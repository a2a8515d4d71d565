"""AOT-compile individual Pallas kernels for a REAL v5e target.

Companion to tools/aot_tpu.py (whole-step oracle): this one answers
per-kernel questions at exactly the shapes the framework's `auto`
routing sends to them on hardware. Mosaic compiling a kernel at its
routed shape is the compiler half of the evidence (the timing half
still needs the chip); a compile FAILURE here means the routing would
break on real hardware, which interpret-mode CPU tests can never
reveal (the b=64 blocked-bwd scoped-VMEM overflow was found exactly
this way). tests/test_tpu_compile.py keeps the main path's cases as
tests (it reuses :func:`kernel_cases` and :func:`compile_case`).

  JAX_PLATFORMS=cpu python tools/aot_kernels.py gru_q_h1760 bigru_h800 ...

Each named case prints one JSON line {case, ok, compile_s, error?}.
With no args, runs the full routed-shape battery.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _aot_common import log, setup_aot_env  # noqa: E402

_log = functools.partial(log, "aot_kernels")


def compile_case(builder, sharding):
    """Lower + compile one case for the device ``sharding`` names (a
    described, not attached, TPU). The kernels' ``interpret`` default
    is False, so the lowering goes through Mosaic."""
    import jax

    fn, args = builder()
    return jax.jit(fn, in_shardings=(sharding,) * len(args)) \
        .lower(*args).compile()


def kernel_cases():
    """case name -> (fn_builder, arg ShapeDtypeStructs). Shapes mirror
    the presets' routed configurations (BASELINE.md chip-suite rows):
    streaming H=800, flagship H=1760, lstm H=1536, AISHELL CTC."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeech_tpu.ops import rnn_pallas as rp
    from deepspeech_tpu.ops import lstm_pallas as lp
    from deepspeech_tpu.ops import ctc_pallas as cp

    S = jax.ShapeDtypeStruct
    b, t = 8, 400  # post-conv frames of ~8 s audio

    def rnnshapes(h, gates, wdt=jnp.bfloat16):
        hN = gates * h
        return (S((b, t, hN), jnp.float32), S((b, t), jnp.float32),
                S((h, hN), wdt), S((hN,), jnp.float32))

    def qshapes(h, gates):
        hN = gates * h
        return (S((b, t, hN), jnp.float32), S((b, t), jnp.float32),
                S((h, hN), jnp.int8), S((hN,), jnp.float32),
                S((hN,), jnp.float32))

    cases = {}

    def gru_case(h, b_=b, t_=t, xdt=jnp.float32, dot="bfloat16",
                 vjp=True, pair=False):
        """Forward + VJP (training), or with ``vjp=False`` the forward
        call alone: what evaluation and offline decode run. ``pair``: a
        whole bidirectional layer, both directions over one ``xproj``
        (the projection's matmul and its float32 bias, handed over
        apart) as ONE function, whose second backward call sums the
        pair's ``dxp`` and its columns."""
        _, _, w, bh = rnnshapes(h, 3)
        xp, m = S((b_, t_, 3 * h), xdt), S((b_, t_), jnp.float32)
        scan = rp.gru_scan_pair_pallas if pair else rp.gru_scan_pallas

        def f():
            def step(*a):
                return scan(*a, dot_dtype=dot)

            def train(*a):
                ys, vjp_ = jax.vjp(step, *a)
                return vjp_(jnp.ones_like(ys))
            return (train if vjp else step), (
                (xp, m) + (bh,) * pair + (w, bh) * (1 + pair))
        return f

    def lstm_case(h):
        xp, m, w, bh = rnnshapes(h, 4)

        def f():
            def step(xp_, m_, w_, bh_):
                return lp.lstm_scan_pallas(xp_, m_, w_, bh_,
                                           dot_dtype="bfloat16")

            def train(xp_, m_, w_, bh_):
                ys, vjp = jax.vjp(step, xp_, m_, w_, bh_)
                return vjp(jnp.ones_like(ys))
            return train, (xp, m, w, bh)
        return f

    def gru_stream_case(h, b_=2, k=32):
        # The serve path's cell (streaming.py _chunk_fn): resident
        # forward with a carried h0, one 64-frame chunk (32 post-conv
        # frames) of a two-stream session.
        hN = 3 * h
        args = (S((b_, k, hN), jnp.bfloat16), S((b_, k), jnp.float32),
                S((h, hN), jnp.float32), S((hN,), jnp.float32),
                S((b_, h), jnp.float32))

        def f():
            def fwd(xp_, m_, w_, bh_, h0_):
                return rp.gru_scan_pallas_stream(xp_, m_, w_, bh_, h0_,
                                                 False, "bfloat16")
            return fwd, args
        return f

    def bigru_case(h):
        xp, m, w, bh = rnnshapes(h, 3)

        def f():
            def fwd(xp_, m_, wf, bf, wb, bb):
                return rp.bigru_scan_pallas(xp_, m_, wf, bf, wb, bb,
                                            False, "bfloat16")
            return fwd, (xp, m, w, bh, w, bh)
        return f

    def gru_q_case(h):
        xp, m, wq, sc, bh = qshapes(h, 3)

        def f():
            def fwd(xp_, m_, wq_, sc_, bh_):
                return rp.gru_scan_pallas_q(xp_, m_, wq_, sc_, bh_,
                                            dot_dtype="bfloat16")
            return fwd, (xp, m, wq, sc, bh)
        return f

    def lstm_q_case(h):
        xp, m, wq, sc, bh = qshapes(h, 4)

        def f():
            def fwd(xp_, m_, wq_, sc_, bh_):
                return lp.lstm_scan_pallas_q(xp_, m_, wq_, sc_, bh_,
                                             dot_dtype="bfloat16")
            return fwd, (xp, m, wq, sc, bh)
        return f

    def ctc_case(vocab, t_, s_):
        import jax.numpy as jnp
        lg = S((4, t_, vocab), jnp.float32)
        lab = S((4, s_), jnp.int32)
        il = S((4,), jnp.int32)
        ll = S((4,), jnp.int32)

        def f():
            def train(lg_, lab_, il_, ll_):
                def loss(lg__):
                    return cp.ctc_loss_pallas(lg__, lab_, il_, ll_).sum()
                return jax.value_and_grad(loss)(lg_)
            return train, (lg, lab, il, ll)
        return f

    def beam_case(merge, w=128, v=4336, t_=400):
        from deepspeech_tpu.decode.beam import beam_search
        lp = S((4, t_, v), jnp.float32)
        lens = S((4,), jnp.int32)

        def f():
            def fwd(lp_, lens_):
                return beam_search(lp_, lens_, beam_width=w,
                                   prune_top_k=40, max_len=200,
                                   merge_impl=merge)
            return fwd, (lp, lens)
        return f

    def gru_q_blocked_case(h):
        xp, m, wq, sc, bh = qshapes(h, 3)

        def f():
            def fwd(xp_, m_, wq_, sc_, bh_):
                return rp.gru_scan_pallas_q(xp_, m_, wq_, sc_, bh_,
                                            dot_dtype="bfloat16",
                                            blocked=True)
            return fwd, (xp, m, wq, sc, bh)
        return f

    def lstm_q_blocked_case(h):
        xp, m, wq, sc, bh = qshapes(h, 4)

        def f():
            def fwd(xp_, m_, wq_, sc_, bh_):
                return lp.lstm_scan_pallas_q(xp_, m_, wq_, sc_, bh_,
                                             dot_dtype="bfloat16",
                                             blocked=True)
            return fwd, (xp, m, wq, sc, bh)
        return f

    def lstmp_case(t_, b_=64, h=2048, p=640):
        """rnnt_he2019's recurrences at the cell's shapes: forward
        with its cell-state tape and the backward kernel."""
        args = (S((b_, t_, 4 * h), jnp.bfloat16), S((b_, t_), jnp.float32),
                S((p, 4 * h), jnp.float32), S((h, p), jnp.float32),
                S((4 * h,), jnp.float32), S((4 * h,), jnp.float32))

        def f():
            def step(*a):
                return lp.lstmp_scan_pallas(*a, dot_dtype="bfloat16")

            def train(*a):
                ys, vjp = jax.vjp(step, *a)
                return vjp(jnp.ones_like(ys))
            return train, args
        return f

    def moe_forward_case(k, n, m, groups):
        """A served program's grouped product: ``moe_gmm`` alone."""
        from deepspeech_tpu.ops import moe_pallas

        args = (S((m, k), jnp.bfloat16), S((groups, k, n), jnp.bfloat16),
                S((groups,), jnp.int32))
        return lambda: (lambda lhs, rhs, sizes: moe_pallas.gmm(
            lhs, rhs, sizes, jnp.bfloat16), args)

    def moe_case(k, n, m=32256, groups=8):
        """lfm2_24b_a2b's grouped products at the cell's row capacity:
        ``moe_gmm`` forward, and through its gradient the transposed
        ``moe_gmm`` and ``moe_tgmm``."""
        from deepspeech_tpu.ops import moe_pallas

        args = (S((m, k), jnp.bfloat16), S((groups, k, n), jnp.bfloat16),
                S((groups,), jnp.int32))

        def f():
            def train(lhs, rhs, sizes):
                out, vjp = jax.vjp(
                    lambda a, b: moe_pallas.gmm(a, b, sizes,
                                                jnp.bfloat16), lhs, rhs)
                return out, vjp(jnp.ones_like(out))
            return train, args
        return f

    def attn_case(window):
        """trinity_large's grouped-query attention over a prefill
        sub-batch in one layer: ``gqa_attn_fwd`` alone, at the module's
        tiles."""
        from deepspeech_tpu.ops import attn_pallas

        args = (S((2, 5250, 8, 6, 128), jnp.bfloat16),
                S((2, 5250, 8, 128), jnp.bfloat16),
                S((2, 5250, 8, 128), jnp.bfloat16))
        return lambda: (lambda q, k, v: attn_pallas.gqa_attention(
            q, k, v, window), args)

    def attn_train_case(window):
        """smallthinker_21b_a3b's grouped-query attention over a step's
        four recordings in one layer, forward and backward:
        ``gqa_attn_fwd`` with its log-sum-exp, ``gqa_attn_bwd_dq`` and
        ``gqa_attn_bwd_dkv``, at the module's tiles (6,784 positions:
        26 query tiles of 256 and one of 128, 13 key tiles of 512 and
        one of 128; 28 / 4 heads of 128)."""
        from deepspeech_tpu.ops import attn_pallas

        args = (S((4, 6784, 4, 7, 128), jnp.bfloat16),
                S((4, 6784, 4, 128), jnp.bfloat16),
                S((4, 6784, 4, 128), jnp.bfloat16))

        def f():
            def train(q, k, v):
                out, vjp = jax.vjp(
                    lambda *x: attn_pallas.gqa_attention(*x, window),
                    q, k, v)
                return out, vjp(jnp.ones_like(out))
            return train, args
        return f

    def attn_decode_case(rows, window):
        """trinity_large's grouped-query attention in one decode step
        of one layer: ``gqa_attn_decode`` alone, 16 streams against
        their cache of ``rows`` rows, at the module's row tile."""
        from deepspeech_tpu.ops import attn_pallas

        args = (S((16, 8, 6, 128), jnp.bfloat16),
                S((16, rows, 8, 128), jnp.bfloat16),
                S((16, rows, 8, 128), jnp.bfloat16),
                S((16,), jnp.int32), S((16,), jnp.bool_))
        return lambda: (lambda q, k, v, pos, live: attn_pallas.gqa_decode(
            q, k, v, pos, live, window), args)

    def ssd_scan_case():
        """falcon_h1_34b's mixer over a prefill sub-batch of one layer:
        ``ssd_chunk_scan`` alone, 32 utterances of 212 positions (2
        chunks of 128, the second hanging over by 44), 32 heads of 128,
        state 256, 2 groups."""
        from deepspeech_tpu.ops import ssd_pallas

        args = (S((32, 212, 32, 128), jnp.bfloat16),
                S((32, 212, 32), jnp.float32), S((32,), jnp.float32),
                S((32, 212, 2, 256), jnp.bfloat16),
                S((32, 212, 2, 256), jnp.bfloat16), S((32,), jnp.float32),
                S((32, 212), jnp.bool_))
        return lambda: (ssd_pallas.chunk_scan, args)

    def ssd_step_case(streams):
        """... and one decode step of one layer: ``ssd_state_step``
        alone, ``streams`` states of 32 x 256 x 128 float32 (4.2 MB
        each), a group's 16 heads (2.1 MB) a grid step, in place."""
        from deepspeech_tpu.ops import ssd_pallas

        args = (S((streams, 32, 256, 128), jnp.float32),
                S((streams, 32, 128), jnp.bfloat16),
                S((streams, 32), jnp.float32), S((32,), jnp.float32),
                S((streams, 2, 256), jnp.bfloat16),
                S((streams, 2, 256), jnp.bfloat16), S((32,), jnp.float32),
                S((streams,), jnp.bool_))
        return lambda: (ssd_pallas.state_step, args)

    def select_decode_case(streams=32, rows=19328):
        """minicpm_sala's sparse layer in one decode step:
        ``gqa_attn_select_decode`` alone, ``streams`` caches of ``rows``
        rows, head-major (2 key/value heads of 128, 16 query heads
        each): the local window's 2,048 rows as one run and an index
        list of 96 blocks of 64 a (stream, head), 16 a grid step."""
        from deepspeech_tpu.ops import attn_pallas

        args = (S((streams, 2, 16, 128), jnp.bfloat16),
                S((streams, 2, rows, 128), jnp.bfloat16),
                S((streams, 2, rows, 128), jnp.bfloat16),
                S((streams, 2, 96), jnp.int32),
                S((streams, 2), jnp.int32), S((streams,), jnp.int32),
                S((streams,), jnp.int32), S((streams,), jnp.bool_))
        return lambda: (lambda *v: attn_pallas.gqa_select_decode(
            *v, 64, 2048), args)

    def select_fwd_case(rows=2, s=15000):
        """... and its sequence form over a prefill sub-batch:
        ``gqa_attn_select_fwd`` alone, 2 recordings of 15,000 positions
        under a selection map of 235 blocks a (query, head)."""
        from deepspeech_tpu.ops import attn_pallas

        args = (S((rows, s, 2, 16, 128), jnp.bfloat16),
                S((rows, s, 2, 128), jnp.bfloat16),
                S((rows, s, 2, 128), jnp.bfloat16),
                S((rows, 2, s, -(-s // 64)), jnp.bool_))
        return lambda: (lambda *v: attn_pallas.gqa_select_attention(*v, 64),
                        args)

    def linear_scan_case(rows=2, s=15000):
        """minicpm_sala's linear-attention layer over a prefill
        sub-batch: ``ssd_chunk_scan`` with a group a head (32 heads of
        128, state 128), no skip, 118 chunks of 128."""
        from deepspeech_tpu.ops import ssd_pallas

        args = (S((rows, s, 32, 128), jnp.bfloat16),
                S((rows, s, 32), jnp.float32), S((32,), jnp.float32),
                S((rows, s, 32, 128), jnp.bfloat16),
                S((rows, s, 32, 128), jnp.bfloat16), S((rows, s), jnp.bool_))
        return lambda: (lambda x, dt, a, b, c, valid: ssd_pallas.chunk_scan(
            x, dt, a, b, c, None, valid), args)

    def linear_step_case(streams=32):
        """... and one decode step: ``ssd_state_step`` with all 32
        single-head groups (2 MB of state) a grid step, in place."""
        from deepspeech_tpu.ops import ssd_pallas

        args = (S((streams, 32, 128, 128), jnp.float32),
                S((streams, 32, 128), jnp.bfloat16),
                S((streams, 32), jnp.float32), S((32,), jnp.float32),
                S((streams, 32, 128), jnp.bfloat16),
                S((streams, 32, 128), jnp.bfloat16),
                S((streams,), jnp.bool_))
        return lambda: (lambda s_, x, dt, a, b, c, live: ssd_pallas.state_step(
            s_, x, dt, a, b, c, None, live,
            group_block=ssd_pallas.head_group_block(32)), args)

    def mhc_case(rows):
        """xing4_29b_a4b's hyper-connection of one sub-layer over
        ``rows`` positions of four bfloat16 streams of 3,584:
        ``mhc_read`` (coefficients and read mix from one tile of 128
        positions, 3.67 MB), a sub-layer that is the identity, and
        ``mhc_write``, at the scoped VMEM the calls compute."""
        from deepspeech_tpu.ops import mhc_pallas

        n, d, count = 4, 3584, 24
        args = (S((rows, n, d), jnp.bfloat16), S((n * d,), jnp.float32),
                S((n * d, count), jnp.float32), S((3,), jnp.float32),
                S((count,), jnp.float32))

        def sub_layer(x, gain, phi, alpha, bias):
            coef, mix = mhc_pallas.read(
                x, gain, phi, alpha, bias, norm_eps=1e-6,
                clamp=(-10.0, 10.0), iters=20, eps=1e-6)
            return coef, mhc_pallas.write(x, mix, coef)

        return lambda: (sub_layer, args)

    # xing4_29b_a4b.transcribe_mtp_16s_b256: a prefill sub-batch's 32 x
    # 212 positions (53 tiles) and a drafting step's 2 x 256 (4 tiles)
    cases["mhc_xing4_prefill"] = mhc_case(6784)
    cases["mhc_xing4_decode"] = mhc_case(512)
    cases["gru_h800"] = gru_case(800)
    cases["gru_h1760"] = gru_case(1760)
    # ds2_full.train_1chip's own call (850 post-conv frames, bf16
    # xproj) and twice its rows: both calls copy their weights into
    # VMEM once, run one grid step a time step and ask for their own
    # scoped VMEM, forward 28 / 28 MiB, backward 32 / 40 MiB.
    cases["gru_h1760_b32"] = gru_case(1760, 32, 850, jnp.bfloat16)
    cases["gru_h1760_b64"] = gru_case(1760, 64, 850, jnp.bfloat16)
    # a whole layer of that cell: both directions as one function of
    # the projection's matmul and its bias, whose second backward call
    # takes the first's float32 dxp rows in and writes the pair's sum
    # in bf16 with its float32 column sums (32 / 40 MiB)
    cases["gru_pair_h1760_b32"] = gru_case(1760, 32, 850, jnp.bfloat16,
                                           pair=True)
    cases["gru_pair_h1760_b64"] = gru_case(1760, 64, 850, jnp.bfloat16,
                                           pair=True)
    # offline decode: the forward call alone, in the 1200-frame bucket
    # and at its widest batch in the 1700-frame one (36 MiB)
    cases["gru_h1760_decode"] = gru_case(1760, 32, 600, jnp.bfloat16,
                                         vjp=False)
    cases["gru_h1760_decode_b128"] = gru_case(1760, 128, 850,
                                              jnp.bfloat16, vjp=False)
    # a float32 model at that width (37.8 MB of weights): the calls'
    # needs pass what a call may pin, or at evaluation's 8 rows come to
    # the cap itself, so both stream 512-column blocks through the
    # BlockSpec pipeline.
    cases["gru_h1760_f32"] = gru_case(1760, dot="float32")
    cases["gru_h1760_f32_b32"] = gru_case(1760, 32, 850, dot="float32")
    cases["gru_stream_h800"] = gru_stream_case(800)
    cases["lstm_h800"] = lstm_case(800)
    cases["lstm_h1536"] = lstm_case(1536)
    cases["bigru_h800"] = bigru_case(800)
    cases["gru_q_h800"] = gru_q_case(800)
    cases["gru_q_h1760"] = gru_q_case(1760)
    cases["lstm_q_h800"] = lstm_q_case(800)
    cases["lstm_q_h1536"] = lstm_q_case(1536)
    # s8 column-streaming forwards at the flagship H: GRU forced past
    # its (natural) int8 residency, LSTM naturally blocked at H=1760.
    cases["gru_q_blocked_h1760"] = gru_q_blocked_case(1760)
    cases["lstm_q_blocked_h1760"] = lstm_q_blocked_case(1760)
    # rnnt_he2019.train_16s_b64: encoder layers 0-1 (567 stacked
    # frames), layers 2-7 (284), prediction net (65 prefixes).
    cases["lstmp_t567_b64"] = lstmp_case(567)
    cases["lstmp_t284_b64"] = lstmp_case(284)
    cases["lstmp_t65_b64"] = lstmp_case(65)
    # lfm2_24b_a2b.train_asr_16s_b128: gate+up (2048 -> 2 x 1536) and
    # down (1536 -> 2048) of 8 held experts over 32,256 rows.
    cases["moe_gmm_w13"] = moe_case(2048, 3072)
    cases["moe_gmm_w2"] = moe_case(1536, 2048)
    # ax_k1.transcribe_16s_b256: gate+up (7168 -> 2 x 2048) and down
    # (2048 -> 7168) of 12 held experts, forward only: a prefill
    # sub-batch's 13,824 static rows (row tiles of 512) and a decode
    # step's 512 (row tiles of 128), K = 7168 as one contraction block.
    for name, m in (("prefill", 13824), ("decode", 512)):
        cases[f"moe_gmm_axk1_{name}_w13"] = moe_forward_case(
            7168, 4096, m, 12)
        cases[f"moe_gmm_axk1_{name}_w2"] = moe_forward_case(
            2048, 7168, m, 12)
    # trinity_large.transcribe_long_7min_b16: gate+up (3072 -> 2 x 3072)
    # and down (3072 -> 3072) of 32 held experts, forward only: a
    # prefill sub-batch's 10,752 static rows (row tiles of 512) and a
    # decode step's 128 (ONE row tile of 128 for about 8 pairs: most of
    # the 32 groups are empty).
    for name, m in (("prefill", 10752), ("decode", 128)):
        cases[f"moe_gmm_trinity_{name}_w13"] = moe_forward_case(
            3072, 6144, m, 32)
        cases[f"moe_gmm_trinity_{name}_w2"] = moe_forward_case(
            3072, 3072, m, 32)
    # trinity_large.transcribe_long_7min_b16: a prefill sub-batch of 2
    # recordings, 5,250 positions (20 query tiles of 256 and one of
    # 130), 48 / 8 heads of 128, a sliding layer and the global one.
    cases["gqa_attn_fwd_trinity_window"] = attn_case(4096)
    cases["gqa_attn_fwd_trinity_global"] = attn_case(0)
    # the same cell's decode step: a sliding layer's ring of 4,096 rows
    # (8 row tiles of 512) and the global layer's 6,784 (14, the last
    # hanging over the cache's end by 384 rows)
    cases["gqa_attn_decode_trinity_window"] = attn_decode_case(4096, 4096)
    cases["gqa_attn_decode_trinity_global"] = attn_decode_case(6784, 0)
    # smallthinker_21b_a3b.train_long_7min: a sliding layer and the
    # global one, forward and backward; and the 16 held experts' gate+up
    # (2560 -> 2 x 768) and down (768 -> 2560) over 61,440 static rows,
    # forward, transposed and ``moe_tgmm``.
    cases["gqa_attn_train_smallthinker_window"] = attn_train_case(4096)
    cases["gqa_attn_train_smallthinker_global"] = attn_train_case(0)
    cases["moe_gmm_smallthinker_w13"] = moe_case(2560, 1536, 61440, 16)
    cases["moe_gmm_smallthinker_w2"] = moe_case(768, 2560, 61440, 16)
    # falcon_h1_34b.transcribe_16s: the mixer's recurrence over a
    # prefill sub-batch and in a decode step of 128 streams
    cases["ssd_chunk_scan_falcon"] = ssd_scan_case()
    cases["ssd_state_step_falcon"] = ssd_step_case(128)
    # minicpm_sala.transcribe_long_20min_b32: the sparse layer under
    # its selection and a linear layer's recurrence, in a prefill
    # sub-batch and in a decode step of 32 streams
    cases["gqa_attn_select_decode_sala"] = select_decode_case()
    cases["gqa_attn_select_fwd_sala"] = select_fwd_case()
    cases["ssd_chunk_scan_sala"] = linear_scan_case()
    cases["ssd_state_step_sala"] = linear_step_case()
    cases["ctc_aishell"] = ctc_case(4336, 400, 60)
    cases["ctc_en"] = ctc_case(29, 400, 160)
    # The weak-#1 shape: AISHELL-width device beam search, both merge
    # strategies — compile proof for the decode path under jit on TPU.
    cases["beam_sort_w128"] = beam_case("sort")
    cases["beam_match_w128"] = beam_case("match")
    return cases


def _stream_cases():
    """``s8_stream`` rows: paired compiles of the blocked-q forward vs
    the fp (f32-stream) blocked forward at the same routed shape. The
    XLA cost-analysis bytes-accessed ratio is the MEASURED form of the
    "in-kernel dequant cuts per-step HBM weight traffic 4×" claim —
    at T=400 the weight re-stream dominates both programs, so the
    whole-program ratio sits just under the per-step 4.0 model. Each
    row also carries the exact analytic per-step weight-stream bytes
    (block layout × stored width), which never depends on the runtime
    exposing a cost model.

    name -> (q_case_builder, fp_case_builder, gates, h).
    """
    import jax
    import jax.numpy as jnp

    from deepspeech_tpu.ops import rnn_pallas as rp
    from deepspeech_tpu.ops import lstm_pallas as lp

    S = jax.ShapeDtypeStruct
    b, t = 8, 400

    def q_fwd(rnn, h):
        gates = 3 if rnn == "gru" else 4
        hN = gates * h
        args = (S((b, t, hN), jnp.float32), S((b, t), jnp.float32),
                S((h, hN), jnp.int8), S((hN,), jnp.float32),
                S((hN,), jnp.float32))

        def f():
            def fwd(xp_, m_, wq_, sc_, bh_):
                if rnn == "gru":
                    return rp.gru_scan_pallas_q(
                        xp_, m_, wq_, sc_, bh_, dot_dtype="bfloat16",
                        blocked=True)
                return lp.lstm_scan_pallas_q(
                    xp_, m_, wq_, sc_, bh_, dot_dtype="bfloat16",
                    blocked=True)
            return fwd, args
        return f

    def fp_fwd(rnn, h):
        gates = 3 if rnn == "gru" else 4
        hN = gates * h
        # f32 weights, f32 dots: the stored/streamed width the int8
        # replicas paid BEFORE in-kernel dequant (the fp working copy).
        args = (S((b, t, hN), jnp.float32), S((b, t), jnp.float32),
                S((h, hN), jnp.float32), S((hN,), jnp.float32))

        def f():
            def fwd(xp_, m_, w_, bh_):
                if rnn == "gru":
                    return rp.gru_scan_pallas(xp_, m_, w_, bh_)
                return lp.lstm_scan_pallas(xp_, m_, w_, bh_)
            return fwd, args
        return f

    return {
        "s8_stream_gru_h1760": (q_fwd("gru", 1760), fp_fwd("gru", 1760),
                                3, 1760),
        "s8_stream_lstm_h1760": (q_fwd("lstm", 1760),
                                 fp_fwd("lstm", 1760), 4, 1760),
    }


def _bytes_accessed(comp):
    """Whole-program bytes-accessed from XLA's cost analysis, or None
    when the runtime does not expose one for this target."""
    try:
        ca = comp.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    try:
        v = ca.get("bytes accessed")
    except AttributeError:
        return None
    return int(v) if v else None


def _stream_step_bytes(gates, h, weight_bytes):
    """Analytic per-step weight-stream bytes at the kernels' actual
    (padded) block layout."""
    from deepspeech_tpu.ops.scan_pallas import block_layout

    n_blocks, c = block_layout(gates * h)
    return n_blocks * c * h * weight_bytes


def main() -> None:
    setup_aot_env()
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    dev = topo.devices[0]
    sh = SingleDeviceSharding(dev)

    cases = kernel_cases()
    stream_cases = _stream_cases()
    names = sys.argv[1:] or (list(cases) + list(stream_cases))
    for name in names:
        if name in stream_cases:
            q_builder, fp_builder, gates, h = stream_cases[name]
            t0 = time.time()
            try:
                q_bytes = _bytes_accessed(compile_case(q_builder, sh))
                fp_bytes = _bytes_accessed(compile_case(fp_builder, sh))
                step_q = _stream_step_bytes(gates, h, 1)
                step_fp = _stream_step_bytes(gates, h, 4)
                rec = {"case": name, "ok": True,
                       "compile_s": round(time.time() - t0, 1),
                       "bytes_accessed": q_bytes,
                       "fp_bytes_accessed": fp_bytes,
                       "weight_stream_bytes_step": step_q,
                       "fp_weight_stream_bytes_step": step_fp,
                       "stream_ratio_model": round(step_fp / step_q, 2),
                       "device_kind": str(dev.device_kind)}
                if q_bytes and fp_bytes:
                    rec["stream_ratio"] = round(fp_bytes / q_bytes, 2)
            except Exception as e:
                rec = {"case": name, "ok": False,
                       "compile_s": round(time.time() - t0, 1),
                       "error": f"{type(e).__name__}: {str(e)[:300]}"}
            print(json.dumps(rec), flush=True)
            continue
        if name not in cases:
            print(json.dumps({"case": name, "ok": False,
                              "error": "unknown case"}))
            continue
        t0 = time.time()
        try:
            comp = compile_case(cases[name], sh)
            ma = comp.memory_analysis()
            rec = {"case": name, "ok": True,
                   "compile_s": round(time.time() - t0, 1),
                   "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                   "device_kind": str(dev.device_kind)}
        except Exception as e:
            rec = {"case": name, "ok": False,
                   "compile_s": round(time.time() - t0, 1),
                   "error": f"{type(e).__name__}: {str(e)[:300]}"}
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
