"""Kernel identity: every Pallas kernel of ``ops/`` is built here, under
a name the device trace carries.

``pl.pallas_call(name=..., metadata=...)`` lowers to a Mosaic custom
call whose instruction is named after the kernel (``%gru_scan_bwd.7``)
and whose ``frontend_attributes={kernel_metadata={...}}`` holds the
facts below. A profiler trace names each device event by its
instruction's text, so a reader finds a kernel, and which call of it,
by name and not by result shape
(``benchmark/layer_metrics/_kernel_id.py``). Everything here is
resolved while jax traces the caller; the kernel's body is unchanged.

One name per kernel ROLE; what tells two builds of a role apart is a
fact, not a name:

  variant  ``resident`` (whole weight matrix a VMEM block), ``pinned``
           (the GRU's float kernels past the residency budget, forward
           and backward: the kernel copies the matrix into a VMEM
           scratch once and every time step, ONE grid step, consumes
           it whole), ``blocked`` (weight columns moved by the
           pipeline over a second grid axis, from wherever XLA left
           the matrix, because a pipelined operand is double-buffered:
           a call whose need reaches ``scan_pallas.PINNED_VMEM_CAP``,
           and the plain LSTM), ``resident_q`` / ``blocked_q`` (as
           ``resident`` and ``blocked``, with int8 weights)
  reverse  1 if the scan runs from the last frame to the first, else
           0; ``both`` for the fused bidirectional kernels
  t, b, h  steps, batch rows and hidden width of the call
  gates    3 (GRU) or 4 (LSTM)
  sum      ``pair`` on the ONE backward call of a bidirectional layer
           (``*_scan_bwd``, the reverse direction's) that takes the
           other direction's float32 ``dxp`` rows in and writes the
           two's sum as its own ``dxp``, in ``xproj``'s type (its
           first result: the one backward call whose ``dxp`` is not
           float32 where ``xproj`` is not), with one more
           ``[8 or 1, G*H]`` result, that sum's column sums
           (``scan_pallas.scan_pair_vjp``); absent on every other call
  p        lstmp_scan_*: width of the recurrent projection
  t, b, s  CTC: frames, padded batch rows, padded extended labels
  m, k, n, groups
           moe_gmm / moe_tgmm: static row capacity, contraction and
           output widths, groups (experts held); moe_gmm also
           ``transpose_rhs`` (1 for the gradient to the rows)
  b, s, kv, rep, head, window
           gqa_attn_fwd: rows, positions, key/value heads, query heads
           a key/value head serves, a head's size, the window (0: the
           layer sees all); ``q_tile`` / ``k_tile``: queries of one
           head and keys a tile; and, a (row, key/value head),
           ``key_tiles`` (key tiles the grid computes, over all query
           tiles), ``key_tiles_in_reach`` (those that hold a key one of
           the tile's queries can reach) and ``key_tiles_masked``
           (those that hold a key out of reach as well: the masked
           work a tile size costs is ``key_tiles x q_tile x k_tile``
           scores against the ones ``s`` and ``window`` need)
           gqa_attn_bwd_dq: the same facts at its own tiles (its grid
           is the forward's). gqa_attn_bwd_dkv: ``b`` .. ``k_tile`` the
           same, ``key_tiles`` the (key tile, query tile) pairs its
           grid computes and ``key_tiles_in_reach`` those that hold a
           pair in reach (all: the guard is exact)
  b, rows, kv, rep, head, window, row_tile, row_tiles
           gqa_attn_decode: streams, rows of a stream's cache (a ring's
           or a full cache's), key/value heads, query heads each
           serves, a head's size, the window (0: the layer sees all),
           cache rows a grid step fetches and the grid's steps a
           stream (how many of them a stream VISITS follows its
           position and is counted by the loop:
           ``lm_rows_fetched_window`` / ``_global``)
  b, rows, kv, rep, head, block, list, per_step
           gqa_attn_select_decode: streams, rows of a stream's cache,
           key/value heads, query heads each serves, a head's size,
           rows a block of the selection, entries of the index list a
           (stream, key/value head) and entries a grid step fetches
           (how many entries are VALID follows the position and is
           counted by the loop: ``lm_select_rows_read``)
  b, s, kv, rep, head, block, q_tile, k_tile, key_tiles
           gqa_attn_select_fwd: as gqa_attn_fwd without a window, and
           rows a block of the selection (every tile at or below the
           diagonal is computed under its queries' selection)
  b, s, heads, head, state, groups, chunk, chunks
           ssd_chunk_scan: rows, positions, the mixer's heads, a head's
           size, the state's size, groups that share B and C, positions
           a chunk and chunks a row (the grid is b x heads x chunks;
           how many positions are VALID follows the lengths and is
           counted by the call: ``lm_valid_positions``)
  b, heads, head, state, groups
           ssd_state_step: streams, heads, a head's size, the state's
           size and groups (the grid is b x groups; how many streams
           are LIVE a step is counted by the loop: ``lm_state_updates``);
           ``group_block`` where a grid step takes several groups (a
           linear-attention layer's groups are single heads)
  n, d, rows, tile, dtype
           mhc_read / mhc_write: residual streams, a stream's width,
           positions of the call (a prefill sub-batch's rows x prefix
           positions, or the positions a decode step verifies),
           positions a grid step takes and the streams' dtype (the grid
           is the ``rows / tile`` tiles, the last one ragged where
           ``tile`` does not divide ``rows``; every position of a call
           is computed, so the count of engagement is the calls')
"""

from __future__ import annotations

from jax.experimental import pallas as pl

KERNELS = frozenset({
    "gru_scan_fwd",       # training/eval forward, one direction
    "gru_scan_bwd",       # its BPTT
    "gru_scan_stream",    # forward with a carried h0 (serving chunks)
    "gru_scan_q_fwd",     # int8-weight forward (inference)
    "gru_scan_q_stream",  # int8-weight forward with a carried h0
    "bigru_scan_fwd",     # both directions in one kernel
    "bigru_scan_bwd",
    "lstm_scan_fwd",
    "lstm_scan_bwd",
    "lstm_scan_q_fwd",
    "lstmp_scan_fwd",     # LSTM with projection (+ layer-normed gates)
    "lstmp_scan_bwd",
    "ctc_alpha",          # alpha recursion, alphas taped for the VJP
    "ctc_alpha_loss",     # alpha recursion, log-likelihood only
    "ctc_gamma",          # beta recursion folded into the occupancies
    "moe_gmm",            # rows by ragged groups times each group's matrix
    "moe_tgmm",           # per group, rows^T times rows: weight gradients
    "gqa_attn_fwd",       # causal grouped-query attention, scores in VMEM
    "gqa_attn_bwd_dq",    # its backward: dq over a query tile's key tiles
    "gqa_attn_bwd_dkv",   # ... dk, dv over a key tile's query tiles and heads
    "gqa_attn_decode",    # one query a stream against its cache rows in reach
    "gqa_attn_select_decode",  # ... against the SELECTED blocks, by index
    "gqa_attn_select_fwd",     # a sequence, each query under its selection
    "ssd_chunk_scan",     # state-space recurrence over a sequence, in chunks
    "ssd_state_step",     # ... one position a stream, the state in place
    "mhc_read",           # hyper-connection: coefficients + the read mix
    "mhc_write",          # ... the streams after the sub-layer, one pass
})


def kernel_call(body, *, kernel: str, facts: dict, **pallas_kwargs):
    """``pl.pallas_call`` under the identity ``kernel`` (one of
    :data:`KERNELS`) with the static ``facts`` of this build."""
    if kernel not in KERNELS:
        raise ValueError(f"{kernel!r} is not in ops.kernel_id.KERNELS")
    return pl.pallas_call(
        body, name=kernel,
        metadata={"kernel": kernel,
                  **{k: str(v) for k, v in facts.items()}},
        **pallas_kwargs)


def scan_facts(variant: str, reverse, t: int, b: int, h: int,
               gates: int) -> dict:
    """The facts every recurrent scan kernel carries."""
    return {"variant": variant,
            "reverse": reverse if isinstance(reverse, str)
            else int(bool(reverse)),
            "t": t, "b": b, "h": h, "gates": gates}
