"""Every Pallas kernel of ``ops/`` carries an identity into the program
text: ``kernel_metadata`` with a ``kernel`` from the closed vocabulary
of ``ops/kernel_id.py`` and the facts of the build. Lowering only (no
Mosaic compile): the attribute is attached when the caller is lowered
for the TPU platform, which needs no chip and takes well under a
second a case. The compiled form is checked in test_tpu_compile.py."""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from deepspeech_tpu.ops import ctc_pallas, kernel_id, rnn_pallas

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

_ATTR = re.compile(r'kernel_metadata = "((?:[^"\\]|\\.)*)"')


def lowered_facts(fn, args) -> list:
    """The ``kernel_metadata`` of every Mosaic call in ``fn`` lowered
    for the TPU, in program order. MLIR prints the attribute as a
    string with ``\\0A`` for a newline and ``\\22`` for a quote."""
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == len(_ATTR.findall(text))
    return [json.loads(m.replace("\\0A", "\n").replace("\\22", '"'))
            for m in _ATTR.findall(text)]


def scan(kernel, variant, reverse, t, b, h, gates=3):
    return {"kernel": kernel, "variant": variant, "reverse": str(reverse),
            "t": str(t), "b": str(b), "h": str(h), "gates": str(gates)}


# tools/aot_kernels.kernel_cases() at b=8, t=400: the five that
# test_tpu_compile.py compiles, then the other routed shapes.
_ST_ATTN = {"b": "4", "s": "6784", "kv": "4", "rep": "7", "head": "128",
            "window": "4096", "q_tile": "256", "k_tile": "512"}

CASES = {
    "gru_h1760": [scan("gru_scan_fwd", "pinned", 0, 400, 8, 1760),
                  scan("gru_scan_bwd", "pinned", 0, 400, 8, 1760)],
    # ds2_full.train_1chip's own call, and twice its rows
    "gru_h1760_b32": [
        scan("gru_scan_fwd", "pinned", 0, 850, 32, 1760),
        scan("gru_scan_bwd", "pinned", 0, 850, 32, 1760)],
    "gru_h1760_b64": [
        scan("gru_scan_fwd", "pinned", 0, 850, 64, 1760),
        scan("gru_scan_bwd", "pinned", 0, 850, 64, 1760)],
    # offline decode: the forward call alone, no VJP
    "gru_h1760_decode": [
        scan("gru_scan_fwd", "pinned", 0, 600, 32, 1760)],
    "gru_h1760_decode_b128": [
        scan("gru_scan_fwd", "pinned", 0, 850, 128, 1760)],
    # a float32 model: the need reaches the cap, both calls stream
    "gru_h1760_f32": [scan("gru_scan_fwd", "blocked", 0, 400, 8, 1760),
                      scan("gru_scan_bwd", "blocked", 0, 400, 8, 1760)],
    "gru_h1760_f32_b32": [
        scan("gru_scan_fwd", "blocked", 0, 850, 32, 1760),
        scan("gru_scan_bwd", "blocked", 0, 850, 32, 1760)],
    "gru_stream_h800": [scan("gru_scan_stream", "resident", 0, 32, 2, 800)],
    "bigru_h800": [scan("bigru_scan_fwd", "resident", "both", 400, 8, 800)],
    "ctc_en": [{"kernel": "ctc_alpha", "t": "400", "b": "8", "s": "384"},
               {"kernel": "ctc_gamma", "t": "400", "b": "8", "s": "384"}],
    "gru_q_h1760": [scan("gru_scan_q_fwd", "resident_q", 0, 400, 8, 1760)],
    "gru_h800": [scan("gru_scan_fwd", "resident", 0, 400, 8, 800),
                 scan("gru_scan_bwd", "resident", 0, 400, 8, 800)],
    "lstm_h800": [scan("lstm_scan_fwd", "resident", 0, 400, 8, 800, 4),
                  scan("lstm_scan_bwd", "resident", 0, 400, 8, 800, 4)],
    "lstm_h1536": [scan("lstm_scan_fwd", "blocked", 0, 400, 8, 1536, 4),
                   scan("lstm_scan_bwd", "blocked", 0, 400, 8, 1536, 4)],
    "lstm_q_h800": [scan("lstm_scan_q_fwd", "resident_q", 0, 400, 8, 800,
                         4)],
    "gru_q_blocked_h1760": [scan("gru_scan_q_fwd", "blocked_q", 0, 400, 8,
                                 1760)],
    "lstm_q_blocked_h1760": [scan("lstm_scan_q_fwd", "blocked_q", 0, 400,
                                  8, 1760, 4)],
    # rnnt_he2019's prediction net: 65 prefixes, b=64, 2048 cells
    # projected to 640.
    "lstmp_t65_b64": [
        {**scan("lstmp_scan_fwd", "resident", 0, 65, 64, 2048, 4),
         "p": "640"},
        {**scan("lstmp_scan_bwd", "resident", 0, 65, 64, 2048, 4),
         "p": "640"}],
    # lfm2_24b_a2b's down projection of 8 held experts over the cell's
    # 32,256 rows: forward, the gradient to the rows (the contraction
    # is then the output width), the weights' gradient.
    "moe_gmm_w2": [
        {"kernel": "moe_gmm", "m": "32256", "k": "1536", "n": "2048",
         "groups": "8", "transpose_rhs": "0"},
        {"kernel": "moe_gmm", "m": "32256", "k": "2048", "n": "1536",
         "groups": "8", "transpose_rhs": "1"},
        {"kernel": "moe_tgmm", "m": "32256", "k": "1536", "n": "2048",
         "groups": "8"}],
    # trinity_large's prefill attention in a sliding layer: a sub-batch
    # of 2 recordings of 5,250 positions; 117 key tiles a (row,
    # key/value head) over 21 query tiles, 26 of them masked
    "gqa_attn_fwd_trinity_window": [
        {"kernel": "gqa_attn_fwd", "b": "2", "s": "5250", "kv": "8",
         "rep": "6", "head": "128", "window": "4096", "q_tile": "256",
         "k_tile": "512", "key_tiles": "117", "key_tiles_in_reach": "117",
         "key_tiles_masked": "26"}],
    # and its decode step: 16 streams against the global layer's cache
    # of 6,784 rows, 14 row tiles of 512 (the last hangs over)
    "gqa_attn_decode_trinity_global": [
        {"kernel": "gqa_attn_decode", "b": "16", "rows": "6784",
         "kv": "8", "rep": "6", "head": "128", "window": "0",
         "row_tile": "512", "row_tiles": "14"}],
    "gqa_attn_decode_trinity_window": [
        {"kernel": "gqa_attn_decode", "b": "16", "rows": "4096",
         "kv": "8", "rep": "6", "head": "128", "window": "4096",
         "row_tile": "512", "row_tiles": "8"}],
    # smallthinker_21b_a3b's attention in training, a sliding layer: 4
    # recordings of 6,784 positions, forward (under ``jax.vjp``: with its
    # log-sum-exp) and the backward pair; 171 (query tile, key tile)
    # pairs a (row, key/value head), the same seen from the keys
    "gqa_attn_train_smallthinker_window": [
        {"kernel": "gqa_attn_fwd", **_ST_ATTN, "key_tiles": "171",
         "key_tiles_in_reach": "171", "key_tiles_masked": "38"},
        {"kernel": "gqa_attn_bwd_dkv", **_ST_ATTN, "key_tiles": "171",
         "key_tiles_in_reach": "171"},
        {"kernel": "gqa_attn_bwd_dq", **_ST_ATTN, "key_tiles": "171",
         "key_tiles_in_reach": "171", "key_tiles_masked": "38"}],
    # falcon_h1_34b's state-space recurrence: a prefill sub-batch of one
    # layer (212 positions in 2 chunks of 128) and a decode step of 128
    # streams
    "ssd_chunk_scan_falcon": [
        {"kernel": "ssd_chunk_scan", "b": "32", "s": "212", "heads": "32",
         "head": "128", "state": "256", "groups": "2", "chunk": "128",
         "chunks": "2"}],
    "ssd_state_step_falcon": [
        {"kernel": "ssd_state_step", "b": "128", "heads": "32",
         "head": "128", "state": "256", "groups": "2"}],
    # minicpm_sala's sparse layer under its selection (a decode step of
    # 32 streams: the local window as one run of 2,048 rows and lists
    # of 96 blocks of 64, 16 a grid step; a prefill
    # sub-batch of 2 x 15,000 positions: every tile at or below the
    # diagonal, 900 a (row, key/value head)) and a linear layer's
    # recurrence with a group a head (118 chunks; all 32 groups a grid
    # step)
    "gqa_attn_select_decode_sala": [
        {"kernel": "gqa_attn_select_decode", "b": "32", "rows": "19328",
         "kv": "2", "rep": "16", "head": "128", "block": "64",
         "window": "2048", "list": "96", "per_step": "16"}],
    "gqa_attn_select_fwd_sala": [
        {"kernel": "gqa_attn_select_fwd", "b": "2", "s": "15000",
         "kv": "2", "rep": "16", "head": "128", "block": "64",
         "q_tile": "256", "k_tile": "512", "key_tiles": "900"}],
    "ssd_chunk_scan_sala": [
        {"kernel": "ssd_chunk_scan", "b": "2", "s": "15000", "heads": "32",
         "head": "128", "state": "128", "groups": "32", "chunk": "128",
         "chunks": "118"}],
    "ssd_state_step_sala": [
        {"kernel": "ssd_state_step", "b": "32", "heads": "32",
         "head": "128", "state": "128", "groups": "32",
         "group_block": "32"}],
    # xing4_29b_a4b's hyper-connection of one sub-layer: a prefill
    # sub-batch's 6,784 positions in 53 tiles of 128, a drafting step's
    # 512 in 4
    "mhc_xing4_prefill": [
        {"kernel": "mhc_read", "n": "4", "d": "3584", "rows": "6784",
         "tile": "128", "dtype": "bfloat16"},
        {"kernel": "mhc_write", "n": "4", "d": "3584", "rows": "6784",
         "tile": "128", "dtype": "bfloat16"}],
    "mhc_xing4_decode": [
        {"kernel": "mhc_read", "n": "4", "d": "3584", "rows": "512",
         "tile": "128", "dtype": "bfloat16"},
        {"kernel": "mhc_write", "n": "4", "d": "3584", "rows": "512",
         "tile": "128", "dtype": "bfloat16"}],
}


@pytest.mark.parametrize("case", list(CASES))
def test_lowered_kernel_carries_its_identity(case):
    from aot_kernels import kernel_cases

    fn, args = kernel_cases()[case]()
    got = lowered_facts(fn, args)
    assert got == CASES[case]
    assert all(f["kernel"] in kernel_id.KERNELS for f in got)


S = jax.ShapeDtypeStruct
_T, _B, _H = 16, 8, 128


def _gru_args(n_w=1):
    return ((S((_B, _T, 3 * _H), jnp.float32), S((_B, _T), jnp.float32))
            + (S((_H, 3 * _H), jnp.float32), S((3 * _H,), jnp.float32))
            * n_w)


def test_reverse_scan_says_so_forward_and_backward():
    def train(xp, m, w, bh):
        ys, vjp = jax.vjp(lambda *a: rnn_pallas.gru_scan_pallas(
            *a, True, False, None), xp, m, w, bh)
        return vjp(ys)

    assert lowered_facts(train, _gru_args()) == [
        scan("gru_scan_fwd", "resident", 1, _T, _B, _H),
        scan("gru_scan_bwd", "resident", 1, _T, _B, _H)]


def _pallas_calls(jaxpr):
    """The parameters of every ``pallas_call`` in a jaxpr, nested
    ones included, in program order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params)
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                found.extend(_pallas_calls(inner))
    return found


@pytest.mark.parametrize("case, kernel, variant, limit_mib", [
    ("gru_h1760", "gru_scan_fwd", "pinned", 24),
    ("gru_h1760", "gru_scan_bwd", "pinned", 28),
    ("gru_h1760_b32", "gru_scan_fwd", "pinned", 28),
    ("gru_h1760_b32", "gru_scan_bwd", "pinned", 32),
    ("gru_h1760_b64", "gru_scan_fwd", "pinned", 28),
    ("gru_h1760_b64", "gru_scan_bwd", "pinned", 40),
    ("gru_h1760_decode", "gru_scan_fwd", "pinned", 28),
    ("gru_h1760_decode_b128", "gru_scan_fwd", "pinned", 36),
    # a float32 model, 37.8 MB of weights: at the cell's rows the need
    # passes the cap, at 8 rows it comes to the cap itself, which is
    # not under it: the pipeline streams
    ("gru_h1760_f32", "gru_scan_fwd", "blocked", None),
    ("gru_h1760_f32", "gru_scan_bwd", "blocked", None),
    ("gru_h1760_f32_b32", "gru_scan_fwd", "blocked", None),
    ("gru_h1760_f32_b32", "gru_scan_bwd", "blocked", None),
])
def test_who_places_the_past_budget_scan_weights(case, kernel, variant,
                                             limit_mib):
    """The copy-once build, forward or backward, says so in its facts,
    takes its weights where XLA left them (``pl.ANY``: no BlockSpec
    pipeline on the operand) and as they are, runs one grid step a
    time step and asks Mosaic for the scoped VMEM its shapes need; at
    the module's cap or past it the call is the streamed one,
    512-column blocks over a second grid axis under Mosaic's default
    limit."""
    from aot_kernels import kernel_cases

    fn, args = kernel_cases()[case]()
    call, = [p for p in _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)
             if p["name"] == kernel]
    assert call["metadata"]["variant"] == variant
    t = int(call["metadata"]["t"])
    # the weights follow the per-step rows: two forward, four backward
    w = call["grid_mapping"].block_mappings[
        {"gru_scan_fwd": 2, "gru_scan_bwd": 4}[kernel]]
    limit = call["compiler_params"].get("mosaic_tpu")
    if limit_mib is None:
        assert "vmem" in str(w.block_aval) and limit is None
        assert [d.block_size for d in w.block_shape] == [1760, 512]
        assert call["grid_mapping"].grid == (t, 11)
    else:
        assert "any" in str(w.block_aval)
        assert [d.block_size for d in w.block_shape] == [1760, 5280]
        assert call["grid_mapping"].grid == (t,)
        assert limit.vmem_limit_bytes == limit_mib * 2 ** 20


def test_every_scan_of_the_ds2_full_step_is_pinned(monkeypatch):
    """ds2_full.train_1chip's model (7 BiGRU-1760, bf16, b=32 in the
    1700-frame bucket), forward and gradient, lowered for the TPU as
    the chip resolves it: 14 forward and 14 backward scans, seven per
    direction, every one placing its own weights and running one grid
    step a time step. None is left to the lottery that made six
    backward calls stream 19.8 MB a time step, to the pipeline's block
    copies out of a matrix XLA had placed, nor to 11 column blocks a
    step of a matrix that sits whole in the kernel's scratch."""
    from collections import Counter

    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models import create_model

    monkeypatch.setenv("DS2N_ASSUME_TPU", "1")
    model = create_model(get_config("ds2_full").model)
    x, lens = S((32, 1700, 161), jnp.float32), S((32,), jnp.int32)
    variables = jax.eval_shape(
        lambda x_, l_: model.init(jax.random.PRNGKey(0), x_, l_,
                                  train=False), x, lens)

    def grads(v, x_, l_):
        return jax.grad(lambda p: jnp.sum(model.apply(
            {**v, "params": p}, x_, l_, train=False)[0]
            .astype(jnp.float32)))(v["params"])

    got = Counter((f["kernel"], f["variant"], f["reverse"], f["t"], f["b"],
                   f.get("sum"))
                  for f in lowered_facts(grads, (variables, x, lens)))
    # ... and of a layer's two backward calls the reverse direction's
    # sums the pair's input gradient (the fact ``sum``), seven a step
    assert got == {
        (kernel, "pinned", r, "850", "32",
         "pair" if (kernel, r) == ("gru_scan_bwd", "1") else None): 7
        for kernel in ("gru_scan_fwd", "gru_scan_bwd") for r in "01"}


def test_the_roles_no_routed_case_reaches():
    def bigru_train(xp, m, wf, bf, wb, bb):
        ys, vjp = jax.vjp(lambda *a: rnn_pallas.bigru_scan_pallas(
            *a, False, None), xp, m, wf, bf, wb, bb)
        return vjp(ys)

    assert [f["kernel"] for f in lowered_facts(
        bigru_train, _gru_args(2))] == ["bigru_scan_fwd", "bigru_scan_bwd"]

    def q_stream(xp, m, wq, sc, bh, h0):
        return rnn_pallas.gru_scan_pallas_q(xp, m, wq, sc, bh, h0=h0)

    xp, m = _gru_args()[:2]
    col = S((3 * _H,), jnp.float32)
    assert lowered_facts(q_stream, (
        xp, m, S((_H, 3 * _H), jnp.int8), col, col,
        S((_B, _H), jnp.float32))) == [
        scan("gru_scan_q_stream", "resident_q", 0, _T, _B, _H)]

    def ctc_eval(lg, lab, il, ll):
        return ctc_pallas.ctc_loss_pallas(lg, lab, il, ll)

    lens = S((4,), jnp.int32)
    assert lowered_facts(ctc_eval, (
        S((4, 40, 29), jnp.float32), S((4, 10), jnp.int32), lens,
        lens)) == [{"kernel": "ctc_alpha_loss", "t": "40", "b": "8",
                    "s": "128"}]


def test_every_name_of_the_vocabulary_is_built_somewhere():
    """The vocabulary is closed both ways: a name nobody builds is a
    reader's dead branch."""
    used = set()
    # the scan kernels' names stand in the route's one table
    for name, named in (("scan_pallas.py", r'="(\w+_scan_\w+)"'),
                        ("ctc_pallas.py", r'kernel="(\w+)"'),
                        ("moe_pallas.py", r'kernel="(\w+)"'),
                        ("attn_pallas.py", r'kernel="(\w+)"'),
                        ("ssd_pallas.py", r'kernel="(\w+)"'),
                        ("mhc_pallas.py", r'kernel="(\w+)"')):
        with open(os.path.join(REPO, "deepspeech_tpu", "ops", name)) as f:
            used.update(re.findall(named, f.read()))
    assert used == kernel_id.KERNELS


def test_a_name_outside_the_vocabulary_is_refused():
    with pytest.raises(ValueError, match="KERNELS"):
        kernel_id.kernel_call(lambda *refs: None, kernel="gru_scan",
                              facts={}, out_shape=S((8, 128), jnp.float32))


def test_no_pallas_call_outside_the_helper():
    ops = os.path.join(REPO, "deepspeech_tpu", "ops")
    for name in sorted(os.listdir(ops)):
        if name.endswith(".py") and name != "kernel_id.py":
            with open(os.path.join(ops, name)) as f:
                assert "pallas_call(" not in f.read(), name
