"""The recurrent scan kernels' one route and one call.

Every fused recurrence of ``ops/`` (GRU and both-direction GRU in
``rnn_pallas.py``, LSTM and LSTM with projection in ``lstm_pallas.py``)
is a sequential time grid over time-major rows with its weights placed
one of five ways. Which way, and whether a kernel runs at all, is
answered HERE and nowhere else (:func:`scan_route`); the
``pl.pallas_call`` that builds the answer is written HERE once
(:func:`scan_call`), with the time index maps, each placement's
``BlockSpec`` layout, the grid, the scratch and the VMEM limit. A cell
type hands in what differs: its gate count and carried states
(``_CELLS``), its element-wise forward and backward math
(:class:`ScanCell`), or, where one step is more than one matmul and an
element-wise update (both directions at once, the projection), its own
step body.

The placements (the ``variant`` fact of ``ops/kernel_id.py``):

``resident``    the matrix is a whole-array VMEM block with a constant
                index map: fetched once, there for every step.
``pinned``      copy-once: the matrix stays where XLA left it
                (``pl.ANY``) and ONE DMA at the first grid step copies
                it into a VMEM scratch; every step then is the resident
                step over the scratch, under the call's own scoped
                limit. ds2_full's build (H=1760, bf16).
``blocked``     streamed: ``BLOCK_COLS``-wide column blocks of the
                matrix over a second grid axis, moved by the BlockSpec
                pipeline every time step; the partial gates land in a
                scratch and the element-wise update fires on the last
                block. The only build a matrix past ``PINNED_VMEM_CAP``
                can run.
``resident_q``  ``resident`` with the int8 matrix and its per-column
                scales; the scale lands on the gates
                (``(h @ Q) * s == h @ (Q * s)``).
``blocked_q``   ``blocked`` with int8 column blocks, upcast in VMEM.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs
from .kernel_id import kernel_call, scan_facts

# The budgets, stated once. What a resident matrix may take of Mosaic's
# default 16 MiB scoped limit (the rest: the per-step rows, twice).
VMEM_WEIGHT_BUDGET = 10 * 1024 * 1024
# Column-block width of the streamed builds (lane-aligned).
BLOCK_COLS = 512
# The scoped VMEM a copy-once call must stay under (a call whose limit
# would reach it streams instead). A v5e core has 128 MiB; the rest
# stays with XLA, which places the neighbouring calls' operands.
PINNED_VMEM_CAP = 48 * 1024 * 1024
# What an lstmp call may ask for, of the same 128 MiB.
LSTMP_VMEM_LIMIT = 96 * 1024 * 1024


class _Cell(NamedTuple):
    gates: int          # column groups of the recurrent matrix
    states: int         # carried states (GRU: h; LSTM: h, c; lstmp: c, r)
    copy_once: bool     # past the residency budget a float matrix may
    #                     be copied once (``pinned``), else it streams
    kernels: dict       # role -> its name in kernel_id.KERNELS


_CELLS = {
    "gru": _Cell(3, 1, True, dict(
        fwd="gru_scan_fwd", bwd="gru_scan_bwd", stream="gru_scan_stream",
        q_fwd="gru_scan_q_fwd", q_stream="gru_scan_q_stream",
        both_fwd="bigru_scan_fwd", both_bwd="bigru_scan_bwd")),
    # whether an LSTM should be copied once is a question of speed no
    # cell asks: it keeps the streamed build it has
    "lstm": _Cell(4, 2, False, dict(
        fwd="lstm_scan_fwd", bwd="lstm_scan_bwd", q_fwd="lstm_scan_q_fwd")),
    "lstmp": _Cell(4, 2, False, dict(
        fwd="lstmp_scan_fwd", bwd="lstmp_scan_bwd")),
}


class ScanRoute(NamedTuple):
    """What runs a recurrence: ``kernel`` is a name of
    ``kernel_id.KERNELS`` or None for the XLA scan, ``variant`` that
    call's ``variant`` fact, ``vmem_limit`` the scoped VMEM it asks
    Mosaic for (None: the default)."""
    kernel: Optional[str]
    variant: Optional[str] = None
    vmem_limit: Optional[int] = None


XLA_SCAN = ScanRoute(None)


def _fits(hidden: int, weight_bytes: int, gates: int) -> bool:
    return gates * hidden * hidden * weight_bytes <= VMEM_WEIGHT_BUDGET


def _bias_grad_rows(rows: int) -> int:
    """Sublanes of the backward call's bias-gradient accumulator: 8
    where the step's ``dgates [rows, G*H]`` is whole sublane tiles (a
    step then adds ``rows / 8`` vectors a lane tile and reduces nothing
    across sublanes; the last 8 -> 1 is the VJP's), else 1 (a row sum
    a step)."""
    return 1 if rows % 8 else 8


def _pinned_vmem_limit(rows: int, hidden: int, cell: _Cell, dot_bytes: int,
                       xproj_bytes: int, backward: bool,
                       sums_pair: bool = False) -> Optional[int]:
    """The scoped-VMEM limit a copy-once call asks for, or None when it
    would reach :data:`PINNED_VMEM_CAP`. What the call holds: ONE copy
    of the matrix (its rows as wide as VMEM's lanes make them), its
    per-step rows twice (the pipeline double-buffers them; backward
    the bias gradient's accumulator with them) and its float32
    scratches, with the step's gate value ``[b, lanes]`` (live whole,
    since one matmul makes it); a quarter on top for the gate
    math's other temporaries, rounded up to 4 MiB and never under
    Mosaic's default of 16 MiB. ds2_full (H=1760, bf16, 18.6 MB of
    weights) at b=32 / 64: forward 28 / 28 MiB, backward 32 / 40 MiB;
    where the call sums the pair (``sums_pair``) the same 32 / 40: the
    other direction's float32 ``dxp`` row comes in too (0.68 MB twice
    at b=32), its own ``dxp`` row leaves as the sum in ``xproj``'s
    type (0.34 MB for 0.68) and the projection's bias gradient has an
    accumulator of its own beside ``db``'s."""
    wide = cell.gates * hidden
    lanes = pl.cdiv(wide, 128) * 128
    # per-step operands (xproj, mask; backward: each state's previous
    # row and dy) and results, as (width, bytes): a row narrower than
    # the lanes takes a whole lane tile
    ins = [(wide, xproj_bytes), (1, 4)]
    outs = [(hidden, 4)]
    whole = 1  # float32 blocks of one sublane tile that stay: the bias
    if backward:
        ins += [(hidden, 4)] * (cell.states + 1)
        # dxp, dgates and the previous state
        outs = [(wide, 4), (wide, 4), (hidden, 4)]
        whole = 2  # and the bias gradient's accumulator, [1 or 8, wide]
        if sums_pair:
            ins += [(wide, 4)]
            outs[0] = (wide, xproj_bytes)
            whole = 3
    row_bytes = (sum(rows * max(w, 128) * n for w, n in ins)
                 + whole * 8 * lanes * 4
                 + sum(rows * w * n for w, n in outs))
    scratch_bytes = rows * 4 * (cell.states * hidden + lanes)
    need = hidden * lanes * dot_bytes + 2 * row_bytes + scratch_bytes
    step = 4 * 1024 * 1024
    limit = max(16 * 1024 * 1024, pl.cdiv(need * 5 // 4, step) * step)
    return limit if limit < PINNED_VMEM_CAP else None


def _lstmp_vmem_bytes(b: int, h: int, p: int, dot_bytes: int,
                      backward: bool) -> int:
    """What an lstmp call holds in VMEM: the single-buffered weights,
    the double-buffered per-step blocks and the float32 [B, 4H]
    temporaries of the gate math (6 forward, 12 backward)."""
    weights = (p * 4 * h + h * p) * dot_bytes + 2 * 4 * h * 4
    row = b * 4 * h
    if backward:
        blocks = 2 * (2 * row * dot_bytes + (2 * b * h + 3 * b * p) * 4)
        return weights + blocks + 12 * row * 4
    blocks = 2 * (row * dot_bytes + (b * h + b * p) * 4)
    return weights + blocks + 6 * row * 4


def scan_route(cell: str, impl: str, *, hidden: int,
               rows: Optional[int] = None, proj: int = 0,
               dot_bytes: int = 4,
               xproj_bytes: Optional[int] = None, int8: bool = False,
               carry: bool = False, directions: int = 1,
               backward: bool = False,
               sums_pair: bool = False) -> ScanRoute:
    """Which kernel runs one layer's recurrence, in which build, from
    what the call can observe. ALL of the choice is here: the callers
    (``models/rnn.py``, ``streaming.py``, ``utils/quantize.py``,
    ``serving/ladder.py``, ``chip_smoke.py``) dispatch on the answer and
    the kernel functions build it.

    ``cell``         ``gru`` / ``lstm`` / ``lstmp``
    ``impl``         the resolved ``rnn_impl``: ``pallas`` or ``xla``
    ``hidden, proj`` cell width; lstmp's projection width
    ``rows``         batch rows of the call (local ones under a mesh).
                     May be left out where the answer does not depend
                     on them; where it does (lstmp, a float GRU past
                     the residency budget) asking without is an error
    ``dot_bytes``    width of the MXU operands (4 / 2): what a float
                     matrix is stored at inside the call
    ``xproj_bytes``  width of the input projection's rows (default: as
                     the dots)
    ``int8``         the matrix arrives int8 with per-column scales
    ``carry``        a state comes in and the final one goes out
                     (chunked streaming, the decoders' one-step path)
    ``directions``   2: both directions' weights are present and float
    ``backward``     the BPTT call of the same layer (a kernel of its
                     own, with more rows a step: its build may differ)
    ``sums_pair``    that BPTT call also takes the other direction's
                     ``dxp`` rows in and writes the pair's sum in
                     ``xproj``'s type, with its column sums
                     (:func:`scan_pair_vjp`): one more row a step and
                     one more accumulator, counted in a copy-once limit
    """
    if impl != "pallas":
        return XLA_SCAN
    facts = _CELLS[cell]
    role = "bwd" if backward else "fwd"

    def known_rows():
        if rows is None:
            raise ValueError(f"the {cell} route at hidden={hidden} "
                             f"depends on the call's rows")
        return rows

    if cell == "lstmp":
        # from a zero carry, float weights, sublane-aligned rows, and
        # both calls under the limit (the backward need is the larger)
        if (carry or int8 or known_rows() % 8 or _lstmp_vmem_bytes(
                rows, hidden, proj, dot_bytes, True) > LSTMP_VMEM_LIMIT):
            return XLA_SCAN
        need = _lstmp_vmem_bytes(rows, hidden, proj, dot_bytes, backward)
        return ScanRoute(facts.kernels[role], "resident", min(
            LSTMP_VMEM_LIMIT, max(32 * 1024 * 1024, need * 5 // 4)))
    if int8:
        # inference only; the carried form is the GRU's and
        # resident-only (a chunk re-enters the kernel)
        resident = _fits(hidden, 1, facts.gates)
        if carry:
            return (ScanRoute(facts.kernels["q_stream"], "resident_q")
                    if resident and "q_stream" in facts.kernels
                    else XLA_SCAN)
        return ScanRoute(facts.kernels["q_fwd"],
                         "resident_q" if resident else "blocked_q")
    if carry:
        return (ScanRoute(facts.kernels["stream"], "resident")
                if "stream" in facts.kernels
                and _fits(hidden, dot_bytes, facts.gates) else XLA_SCAN)
    if (directions == 2 and "both_" + role in facts.kernels
            and _fits(hidden, dot_bytes, 2 * facts.gates)):
        # both directions' matrices resident at once: one fused kernel
        return ScanRoute(facts.kernels["both_" + role], "resident")
    kernel = facts.kernels[role]
    if _fits(hidden, dot_bytes, facts.gates):
        return ScanRoute(kernel, "resident")
    if facts.copy_once:
        limit = _pinned_vmem_limit(
            known_rows(), hidden, facts, dot_bytes,
            dot_bytes if xproj_bytes is None else xproj_bytes, backward,
            sums_pair)
        if limit is not None:
            return ScanRoute(kernel, "pinned", limit)
    return ScanRoute(kernel, "blocked")


def own_route(role: str, cell: str, **facts) -> ScanRoute:
    """The route of a call made straight to a kernel function, the
    cell's kernel of this ``role``: the one :func:`scan_route` names,
    which has to be that kernel. No kernel function builds a call the
    route would not send to it."""
    route = scan_route(cell, "pallas", **facts)
    kernel = _CELLS[cell].kernels[role]
    if route.kernel != kernel:
        raise ValueError(
            f"{kernel} cannot run this call"
            + (" (a carried state is resident-only)"
               if facts.get("carry") else "")
            + f": scan_route names {route.kernel or 'the XLA scan'} for "
            + ", ".join(f"{k}={v}" for k, v in facts.items()))
    return route


# ---------------------------------------------------------------------------
# The scaffold: operands, index maps, layouts, the call.
# ---------------------------------------------------------------------------

def dot_jnp_dtype(dot_dtype: Optional[str]):
    if dot_dtype is None or dot_dtype == "float32":
        return jnp.float32
    if dot_dtype == "bfloat16":
        return jnp.bfloat16
    # Fail loudly rather than silently computing in a different
    # precision than the XLA path would.
    raise ValueError(f"unsupported pallas dot_dtype {dot_dtype!r}; "
                     "use None/'float32'/'bfloat16'")


def time_major(xproj, mask):
    """(xp_t [T,B,G], mask_t [T,B,1]) kernel operands.

    xproj keeps its incoming dtype: a bf16 model hands bf16 xproj in,
    and storing it unwidened halves the dominant per-step VMEM stream
    (kernel adds promote to f32 — identical math to upcasting here).
    The mask's trailing singleton keeps the per-step block's last two
    dims equal to the array dims, which real-TPU lowering requires
    (a (1, B) block over a (T, B) array has an unaligned sublane dim).
    """
    return (jnp.moveaxis(xproj, 1, 0),
            jnp.moveaxis(mask.astype(jnp.float32), 1, 0)[..., None])


def time_index_maps(t_max: int, reverse: bool):
    """The per-step rows' index maps ``(at, at_bptt, at_prev)``, grid
    step -> block index. Direction lives in them alone: the reversed
    scan runs t = T-1 .. 0, so scan step i touches row T-1-i and no
    operand is flipped. BPTT runs opposite to the scan: its grid step i
    is scan step T-1-i (``at_bptt``), whose previous state is the row
    of scan step T-2-i (``at_prev``; out of range at i == T-1, where
    the kernels take the zero state, so the index is clamped)."""
    row = (lambda t: t_max - 1 - t) if reverse else (lambda t: t)
    at = lambda t: (row(t), 0, 0)
    return (at, lambda i: at(t_max - 1 - i),
            lambda i: at(jnp.maximum(t_max - 2 - i, 0)))


def prev_sequence(ys, reverse: bool):
    """``h_{t-1}`` for every row of ``ys [T, B, H]`` in scan order: the
    sequence shifted by one scan step from the zero state."""
    zero = jnp.zeros_like(ys[:1])
    return (jnp.concatenate([ys[1:], zero], axis=0) if reverse
            else jnp.concatenate([zero, ys[:-1]], axis=0))


def block_layout(cols: int):
    """(n_blocks, block_cols) of the streamed builds' column grid."""
    c = min(BLOCK_COLS, pl.cdiv(cols, 128) * 128)
    return pl.cdiv(cols, c), c


def _pad_cols(x, cols: int):
    pad = cols - x.shape[-1]
    return x if pad == 0 else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def recurrent_dw(h_prev, dgates, dot):
    """``dW_h = sum over T*B of h_prev^T dgates``: the recurrent weight
    gradient as one MXU contraction of two float32 ``[T, B, .]`` (or
    flat ``[T * B, .]``) sequences outside the time loop, at the
    precision the scan's dot type states. The sum is cancellation-heavy
    (T*B = 27,200 products an entry at ds2_full's cell), so it never
    rounds an operand to 8 bits (``DEFAULT``, one bf16 pass: 3.6e-2 off
    the float32 truth at toy size, tests/test_pallas.py
    test_gru_bf16_dw_closer_to_truth_than_oracle).

    float32 dots: ``HIGHEST``, six bf16 passes, 24 bits of each operand
    (a float32 model states float32 compute). bfloat16 dots: ``HIGH``,
    three passes (``hi*hi + hi*mid + mid*hi``), 16 bits of each
    operand: both operands come out of T steps of bf16 matmuls, whose
    noise puts dW_h 3.2e-4 from the all-float32 program's, and three
    passes are 1.4e-5 from the float64 sum, 23 times under it (the
    chip at the cell's shape, ``chip_smoke.py dw_h_precision``; limits
    and readings: PERF.md section 6, PR 37). The last 8 bits that
    ``HIGHEST`` would carry are bits of that noise, at twice the MXU
    time: 14 such contractions were 41% of ds2_full's step."""
    precision = (jax.lax.Precision.HIGH if dot == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)
    with jax.named_scope("dw_h"):  # obs/layers.py reads its time by it
        return jnp.einsum("...h,...g->hg", h_prev, dgates,
                          precision=precision)


def scan_call(body, route: ScanRoute, *, reverse, hidden: int, gates: int,
              rows, weights, carry=(), outs, whole_outs=(), scratch,
              interpret: bool = False, more_facts=None,
              buffer_weights_once: bool = False,
              dimension_semantics=None):
    """THE ``pallas_call`` of the recurrent scan kernels: ``body`` under
    the identity and in the build ``route`` names.

    ``rows``        per-step operands, ``(array [T, b, X], index map)``
    ``weights``     the operands the variant places, the recurrent
                    matrix first, its ``[1, cols]`` columns (scales,
                    bias) after it
    ``carry``       ``[b, X]`` arrays that enter whole
    ``outs``        per-step results ``(width, dtype, index map)``, each
                    ``[T, b, width]``; with a fourth field True, flat:
                    ``[T * b, width]``, a step's rows one block of it
                    (``b`` whole sublane tiles)
    ``whole_outs``  shapes of float32 results written whole (the final
                    carry, gradients accumulated over the grid)
    ``scratch``     ``cols -> widths`` of the float32 ``[b, n]``
                    scratches (``cols``: the gate columns as the build
                    pads them)

    The body's refs come in this order, and after the scratches a
    ``pinned`` build's matrix scratch and DMA semaphore. A streamed
    build's body is also handed ``n_blocks`` and ``c``. Returns the
    list of results, per-step ones first. ``more_facts``: lstmp's
    ``p``, the pair's ``sum``. What only lstmp sets today:
    ``buffer_weights_once`` (whole blocks under ``pl.Buffered(1)``)
    and ``dimension_semantics``.
    """
    t_max, b = rows[0][0].shape[:2]
    whole = lambda shape, **kw: pl.BlockSpec(
        shape, lambda *_: (0,) * len(shape), memory_space=pltpu.VMEM, **kw)
    grid, on_grid, cols = (t_max,), lambda imap: imap, weights[0].shape[1]
    placed, params = [], {}
    once = ({"pipeline_mode": pl.Buffered(1)} if buffer_weights_once
            else {})  # a block that never moves needs no second buffer
    w_specs = [whole(w.shape, **once) for w in weights]
    if route.variant == "pinned":
        w_specs[0] = pl.BlockSpec(memory_space=pl.ANY)
        placed = [pltpu.VMEM(weights[0].shape, weights[0].dtype),
                  pltpu.SemaphoreType.DMA(())]
    elif route.variant.startswith("blocked"):
        # a pipelined operand is double-buffered: two column blocks fit
        # where two whole matrices do not
        n_blocks, c = block_layout(cols)
        cols, grid = n_blocks * c, (t_max, n_blocks)
        body = functools.partial(body, n_blocks=n_blocks, c=c)
        on_grid = lambda imap: lambda t, g: imap(t)
        w_specs = [pl.BlockSpec((w.shape[0], c), lambda t, g: (0, g),
                                memory_space=pltpu.VMEM) for w in weights]
        weights = [_pad_cols(w, cols) for w in weights]
    if dimension_semantics is not None:
        params["dimension_semantics"] = dimension_semantics
    if route.vmem_limit is not None:
        params["vmem_limit_bytes"] = route.vmem_limit
    step = lambda width, imap: pl.BlockSpec(
        (1, b, width), on_grid(imap), memory_space=pltpu.VMEM)
    flat_step = lambda width, imap: pl.BlockSpec(
        (b, width), on_grid(lambda t: imap(t)[:2]), memory_space=pltpu.VMEM)
    outs = [(*out, False)[:4] for out in outs]
    return kernel_call(
        body, kernel=route.kernel,
        facts={**scan_facts(route.variant, reverse, t_max, b, hidden, gates),
               **(more_facts or {})},
        grid=grid,
        in_specs=([step(x.shape[2], imap) for x, imap in rows] + w_specs
                  + [whole(x.shape) for x in carry]),
        out_specs=([(flat_step if flat else step)(width, imap)
                    for width, _, imap, flat in outs]
                   + [whole(shape) for shape in whole_outs]),
        out_shape=([jax.ShapeDtypeStruct(
            (t_max * b, width) if flat else (t_max, b, width), dtype)
            for width, dtype, _, flat in outs]
                   + [jax.ShapeDtypeStruct(shape, jnp.float32)
                      for shape in whole_outs]),
        scratch_shapes=[pltpu.VMEM((b, n), jnp.float32)
                        for n in scratch(cols)] + placed,
        interpret=interpret,
        **({"compiler_params": pltpu.CompilerParams(**params)}
           if params else {}),
    )(*[x for x, _ in rows], *weights, *carry)


# ---------------------------------------------------------------------------
# The gated cells (GRU, LSTM): one matmul and an element-wise update a
# step. The step bodies of every build, over the cell's element-wise
# math; the forward call, the backward call and the custom_vjp wiring.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ScanCell:
    """A gated cell's own math, float32 ``[B, .]`` values in and out.

    ``fwd(xp, gates, states, m) -> new states``: the update from the
    input projection's row, the recurrent gates ``[B, G*H]``, the
    carried states and the mask column; padded frames hold the states.
    The first state is the one the recurrent matmul consumes and the
    layer's output.

    ``bwd(xp, gates, prev_states, m, dstates, dy) -> (dxp, dgates,
    dprev)``: one BPTT step from recomputed gates. ``dprev`` lacks the
    ``dgates @ W^T`` term of the first state, which is the body's."""
    name: str
    fwd: Callable
    bwd: Callable


def _copy_weights_once(w_ref, w_scr, sem):
    """The copy-once build's one DMA: ``w_ref`` is the whole matrix
    wherever XLA left it (``pl.ANY``), copied into the VMEM scratch
    ``w_scr`` at the call's first grid step; every later step reads the
    scratch."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        copy = pltpu.make_async_copy(w_ref, w_scr, sem)
        copy.start()
        copy.wait()


def _recurrent_gates(state, w_refs, dot):
    """``state @ W + b`` in float32 for the matrix (or column block)
    in ``w_refs``: ``(w, bias)`` in the dot type, or int8
    ``(w, scale, bias)``, whose values convert to ``dot`` losslessly
    (|q| <= 127 is exact even in bf16) and whose scale lands on the
    product."""
    if len(w_refs) == 2:
        w_ref, b_ref = w_refs
        return jnp.dot(state.astype(w_ref.dtype), w_ref[:],
                       preferred_element_type=jnp.float32) + b_ref[:]
    w_ref, sc_ref, b_ref = w_refs
    return jnp.dot(state.astype(dot), w_ref[:].astype(dot),
                   preferred_element_type=jnp.float32) \
        * sc_ref[:] + b_ref[:]


def _through_gates(dgates, w_ref):
    """``dgates @ W^T``: the gates' share of the first state's
    gradient."""
    return jax.lax.dot_general(
        dgates.astype(w_ref.dtype), w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _fwd_step(cell: ScanCell, variant: str, n_weights: int, carried: bool,
              dot, *refs, n_blocks: int = 1, c: int = 0):
    """One forward grid step. refs: xproj row, mask row, the weights,
    [the carried-in states], per-step results (the new states; only as
    many as the call tapes), [the final states], the state scratches,
    [streamed: the gates' scratch], [pinned: matrix scratch, semaphore].

    Streamed builds run a time step as ``n_blocks`` grid steps: each
    lands its column block's gates in the scratch and the update fires
    on the last."""
    n = _CELLS[cell.name].states
    (xp_ref, mask_ref), w_refs = refs[:2], refs[2:2 + n_weights]
    refs = refs[2 + n_weights:]
    if variant == "pinned":
        *refs, w_scr, sem = refs
        _copy_weights_once(w_refs[0], w_scr, sem)
        w_refs = (w_scr,) + tuple(w_refs[1:])
    blocked = variant.startswith("blocked")
    if blocked:
        *refs, gates_buf = refs
    h0_refs, refs = (refs[:n], refs[n:]) if carried else ((), refs)
    state_refs, refs = refs[len(refs) - n:], refs[:len(refs) - n]
    # per-step results, then (carried) as many final states as came in
    step_refs = refs[:len(refs) - len(h0_refs)]
    final_refs = refs[len(step_refs):]
    t = pl.program_id(0)
    start = t == 0
    if blocked:
        g = pl.program_id(1)
        start = start & (g == 0)

    @pl.when(start)
    def _():
        for i, s in enumerate(state_refs):
            s[:] = h0_refs[i][:] if carried else jnp.zeros_like(s)

    first = state_refs[0][:]
    gates = _recurrent_gates(first, w_refs, dot)

    def update(gates):
        states = (first,) + tuple(s[:] for s in state_refs[1:])
        new = cell.fwd(xp_ref[0], gates(), states, mask_ref[0])
        for s, v in zip(state_refs, new):
            s[:] = v
        for o, v in zip(step_refs, new):
            o[0] = v
        if carried:
            @pl.when(t == pl.num_programs(0) - 1)
            def _():
                for o, v in zip(final_refs, new):
                    o[:] = v

    if blocked:
        gates_buf[:, pl.ds(g * c, c)] = gates
        width = _CELLS[cell.name].gates * first.shape[-1]
        pl.when(g == n_blocks - 1)(
            lambda: update(lambda: gates_buf[:, :width]))
    else:
        update(lambda: gates)


def _add_column_sums(acc_ref, rows):
    """One step's ``rows [b, G*H]`` into a gradient accumulator of
    :func:`_bias_grad_rows`' form."""
    if acc_ref.shape[0] == 1:
        acc_ref[:] += jnp.sum(rows, axis=0, keepdims=True)
    else:  # whole sublane tiles: vector adds, nothing across them
        acc_ref[:] += functools.reduce(jnp.add, [
            rows[i:i + 8] for i in range(0, rows.shape[0], 8)])


def _bwd_step(cell: ScanCell, variant: str, sums_pair: bool, *refs,
              n_blocks: int = 1, c: int = 0):
    """One reverse-time BPTT grid step (flash-style gate recompute):
    carries the states' gradients across steps and recomputes the gates
    from (previous states, xproj, W) rather than storing them. Streams
    per-step ``dxp`` and ``dgates`` out; dW is formed outside as one
    contraction over the streamed dgates (a single large MXU
    contraction beats a [H, G*H] VMEM accumulator, which would not
    leave room for W). The bias gradient is this call's: ``db``
    ``[8 or 1, G*H]`` (:func:`_bias_grad_rows`) stays in VMEM over the
    grid and takes every step's ``dgates`` while they are there, so no
    second pass reads the streamed ``[T, b, G*H]`` for its column sums
    (14 such XLA reductions over 574 MB each were 10.8 ms of
    ds2_full's step, and the sums add 0.005 ms to this call's 8.17:
    PERF.md section 6, PR 47). So is dW's other operand: the first
    state's previous row, which the step fetches for the gate
    recompute with the zero state at the scan's start in it, goes out
    again as ``h_prev``'s row, so the VJP builds no shifted copy of the
    state sequence (a slice and a layout turn of 191 MB, 14 times a
    step of ds2_full: PERF.md section 6, PR 48).

    ``sums_pair``: this is the second backward call of a bidirectional
    layer's pair (:func:`scan_pair_vjp`), and it writes what the input
    projection's backward READS. The first call's float32 ``dxp`` row
    of the same frames comes in as one more per-step operand, and the
    ``dxp`` row that goes out is the projection's whole gradient in
    its final form: ``other + own`` in float32, rounded once, in VMEM,
    to ``xproj``'s type (the result's). The float32 sum's rows go into
    a second accumulator ``dbx`` of ``db``'s form on the way: the
    projection's bias gradient. So no pass outside the kernels reads a
    ``[T, b, G*H]`` cotangent but the projection's own contractions.
    XLA made each of these in a pass of its own, seven times a step of
    ds2_full: the sum over 2 x 574 MB float32 (PERF.md section 6,
    PR 50), the float32 sum read back and rounded to bf16 (8.88 ms a
    step) and one more reading for the bias' column sums (5.42 ms;
    PERF.md section 6, PR 55). Nothing else of the step differs.

    refs: xproj row, mask row, each state's previous row, dy row,
    [``sums_pair``: the other direction's dxp row], W, bias, dxp row,
    dgates row, h_prev row (a ``(1, b, H)`` block of ``[T, b, H]`` or
    the ``(b, H)`` block of a flat result), db, [``sums_pair``: dbx],
    the state gradients' scratches, [streamed: dh_acc, gates_buf,
    dg_prev], [pinned: matrix scratch, semaphore].

    Resident and copy-once: the step's ``dgates @ W^T`` goes into the
    carried gradient straight away (the matrix is whole in VMEM; read
    once per matmul with the stores between them, where one read
    feeding both makes Mosaic hold it a second time: 42 MiB of scoped
    VMEM for 24 at ds2_full's b=32; and this order reads 8.24 ms a call
    there against 8.36 one step behind: PERF.md section 6, PR 31).
    Streamed: ONE pass over the weight blocks a time step; the
    contraction uses the PREVIOUS step's dgates (``dg_prev``) so it
    rides the same pass as the gate recompute, the carried gradient
    holds the element-wise part only and the whole assembles at the
    last block."""
    n = _CELLS[cell.name].states
    (xp_ref, mask_ref), prev_refs = refs[:2], refs[2:2 + n]
    dy_ref, *refs = refs[2 + n:]
    other_dxp_ref = refs.pop(0) if sums_pair else None
    w_ref, b_ref, dxp_ref, dgates_ref, hprev_ref, db_ref = refs[:6]
    scratch = refs[6:]
    dbx_ref = scratch.pop(0) if sums_pair else None
    if variant == "pinned":
        *scratch, w_scr, sem = scratch
        _copy_weights_once(w_ref, w_scr, sem)
        w_ref = w_scr
    blocked = variant == "blocked"
    dstate_refs = scratch[:n]
    ti = pl.program_id(0)  # 0.. T-1, processing scan step T-1-ti
    start = ti == 0
    if blocked:
        dh_acc, gates_buf, dg_prev = scratch[n:]
        g = pl.program_id(1)
        start = start & (g == 0)

    @pl.when(start)
    def _():
        for d in (*dstate_refs, db_ref, *([dbx_ref] if sums_pair else [])):
            d[:] = jnp.zeros_like(d)
        if blocked:
            dg_prev[:] = jnp.zeros_like(dg_prev)

    if blocked:
        @pl.when(g == 0)
        def _():
            dh_acc[:] = jnp.zeros_like(dh_acc)

    scan_start = ti == pl.num_programs(0) - 1  # the zero initial state
    prev = lambda ref: jnp.where(scan_start, jnp.zeros_like(ref[0]), ref[0])
    first = prev(prev_refs[0])
    gates = _recurrent_gates(first, (w_ref, b_ref), None)

    def update(gates, dfirst):
        prevs = (first,) + tuple(prev(r) for r in prev_refs[1:])
        dxp, dgates, dprev = cell.bwd(
            xp_ref[0], gates(), prevs, mask_ref[0],
            (dfirst(),) + tuple(d[:] for d in dstate_refs[1:]), dy_ref[0])
        if sums_pair:
            dxp = other_dxp_ref[0] + dxp
            _add_column_sums(dbx_ref, dxp)
        dxp_ref[0] = dxp.astype(dxp_ref.dtype)
        dgates_ref[0] = dgates
        if len(hprev_ref.shape) == 3:  # a row of [T, b, H]
            hprev_ref[0] = first
        else:  # b rows of the flat [T * b, H]
            hprev_ref[:] = first
        _add_column_sums(db_ref, dgates)
        return dgates, dprev

    if not blocked:
        dgates, dprev = update(lambda: gates, lambda: dstate_refs[0][:])
        dstate_refs[0][:] = dprev[0] + _through_gates(dgates, w_ref)
        for d, v in zip(dstate_refs[1:], dprev[1:]):
            d[:] = v
        return

    gates_buf[:, pl.ds(g * c, c)] = gates
    dg_block = dg_prev[:, pl.ds(g * c, c)]
    dh_acc[:] += _through_gates(dg_block, w_ref)

    @pl.when(g == n_blocks - 1)
    def _():
        width = _CELLS[cell.name].gates * first.shape[-1]
        dgates, dprev = update(lambda: gates_buf[:, :width],
                               lambda: dstate_refs[0][:] + dh_acc[:])
        dg_prev[:, :width] = dgates
        for d, v in zip(dstate_refs, dprev):
            d[:] = v


def scan_forward(cell: ScanCell, xproj, mask, w, b_h, *, reverse=False,
                 interpret=False, dot_dtype=None, scale=None, h0=None,
                 tape: bool = False, blocked: Optional[bool] = None):
    """The forward call of a gated cell in the build the route names.

    ``w [H, G*H]`` float (cast to the dot type) or, with ``scale``,
    int8; ``h0 [B, H]`` seeds the scan and adds the final carry to the
    results; ``tape`` returns every state's sequence (BPTT's
    residuals), else the first alone; ``blocked`` True/False forces the
    int8 build (tests, the AOT traffic legs). Returns
    ``(results, xp_t, mask_t)``, the results time-major."""
    gates, n = _CELLS[cell.name].gates, _CELLS[cell.name].states
    b, t_max, wide = xproj.shape
    h = wide // gates
    dot = dot_jnp_dtype(dot_dtype)
    int8, carried = scale is not None, h0 is not None
    if int8 and w.dtype != jnp.int8:
        raise ValueError(f"w_q must be int8, got {w.dtype}")
    kind = ("q_" if int8 else "") + ("stream" if carried else "fwd")
    facts = dict(rows=b, hidden=h, dot_bytes=jnp.dtype(dot).itemsize,
                 xproj_bytes=xproj.dtype.itemsize, int8=int8,
                 carry=carried)
    if carried and blocked:
        raise ValueError(f"int8 fused {cell.name.upper()} with a carried "
                         f"state (streaming) is resident-only: the "
                         f"blocked-q build has no h0 form")
    route = own_route(kind, cell.name, **facts)
    if blocked is False and route.variant != "resident_q":
        raise ValueError(
            f"int8 fused {cell.name.upper()} forced resident "
            f"(blocked=False) but H={h} exceeds the 1-byte residency "
            f"budget")
    if blocked:
        route = route._replace(variant="blocked_q")
    xp_t, mask_t = time_major(xproj, mask)
    at, _, _ = time_index_maps(t_max, reverse)
    column = lambda v: v.astype(jnp.float32).reshape(1, wide)
    columns = ([column(scale)] if int8 else []) + [column(b_h)]
    weights = [w if int8 else w.astype(dot)] + columns
    streamed = route.variant.startswith("blocked")
    results = scan_call(
        functools.partial(_fwd_step, cell, route.variant, len(weights),
                          carried, dot),
        route, reverse=reverse, hidden=h, gates=gates,
        rows=[(xp_t, at), (mask_t, at)], weights=weights,
        carry=[h0.astype(jnp.float32)] if carried else [],
        outs=[(h, jnp.float32, at)] * (n if tape else 1),
        whole_outs=[(b, h)] * (n if carried else 0),
        scratch=lambda cols: [h] * n + ([cols] if streamed else []),
        interpret=interpret)
    return results, xp_t, mask_t


def _scan_backward(cell: ScanCell, residuals, dy_t, *, reverse, interpret,
                   dot_dtype, other_dxp_t=None):
    """The backward call of one direction and what the VJP makes of its
    results: ``(dxp_t [T, b, G*H], dW_h, db_h, db_x)`` from the forward
    call's ``residuals`` and the time-major float32 cotangent ``dy_t``.
    The call hands back what it holds in VMEM beside ``dxp``: both
    operands of ``dW_h`` (``dgates`` and ``h_prev``, the first state
    one scan step back from the zero state: :func:`prev_sequence`'s
    rows bit for bit, as ``[T * b, H]`` where the contraction then
    takes both as they lie) and the bias gradient's sums.

    ``other_dxp_t``: the float32 ``dxp_t`` of the layer's other
    direction. The call then takes its rows in beside its own
    (``_bwd_step``'s ``sums_pair``), its ``dxp_t`` is the pair's sum
    in ``xproj``'s type and ``db_x [G*H]`` float32 that sum's column
    sums over ``T * b`` (the input projection's bias gradient, summed
    before the rounding); without it, ``dxp_t`` is its own in float32
    (what a summing call reads) and ``db_x`` None.

    While jax traces it, it is recorded as the gauges
    ``scan_bias_grad{kernel, variant, form}``, ``form`` the
    accumulator's (``rows8`` / ``rows1``), ``scan_prev_state{kernel,
    variant, source="kernel", rows}``, ``rows`` how ``h_prev`` is laid
    out (``flat`` / ``stepped``), ``scan_input_grad{kernel, variant,
    sum, dtype}``: whose ``dxp`` the call writes (``pair`` / ``own``)
    and in what type, and, on the summing call alone,
    ``scan_proj_bias_grad{kernel, variant, source="kernel"}``."""
    gates, n = _CELLS[cell.name].gates, _CELLS[cell.name].states
    xp_t, mask_t, w_h, b_h, *seqs = residuals
    t_max, b, h = seqs[0].shape
    dot = dot_jnp_dtype(dot_dtype)
    sums_pair = other_dxp_t is not None
    bh2 = b_h.astype(jnp.float32).reshape(1, gates * h)
    w = w_h.astype(dot)
    _, at_bptt, at_prev = time_index_maps(t_max, reverse)
    route = own_route(
        "bwd", cell.name, rows=b, hidden=h,
        dot_bytes=jnp.dtype(dot).itemsize,
        xproj_bytes=xp_t.dtype.itemsize, backward=True,
        sums_pair=sums_pair)
    streamed = route.variant == "blocked"
    db_rows = _bias_grad_rows(b)
    # Whole sublane tiles of rows: h_prev leaves the kernel flat,
    # [T * b, H], a step's rows one block of it, and XLA contracts
    # it with dgates' same rows (a bitcast) over T * b as they lie.
    # Handed two [T, b, .] arrays it contracts over T with b as a
    # window and turns both operands batch-major first, on the
    # contraction (+0.84 ms a call at ds2_full's shape) or as a
    # pass of its own, and two reshapes outside the kernel it folds
    # back into that form (PERF.md section 6, PR 48). dgates stays
    # [T, b, G*H], a bitcast away from those rows.
    flat = not b % 8
    # the pair's sum leaves as xproj reads it; an own dxp float32, as
    # the summing call takes it in
    dxp_dtype = xp_t.dtype if sums_pair else jnp.dtype(jnp.float32)
    build = {"kernel": route.kernel, "variant": route.variant}
    obs.registry().gauge("scan_bias_grad", 1, labels={
        **build, "form": f"rows{db_rows}"})
    obs.registry().gauge("scan_prev_state", 1, labels={
        **build, "source": "kernel",
        "rows": "flat" if flat else "stepped"})
    obs.registry().gauge("scan_input_grad", 1, labels={
        **build, "sum": "pair" if sums_pair else "own",
        "dtype": dxp_dtype.name})
    if sums_pair:
        obs.registry().gauge("scan_proj_bias_grad", 1, labels={
            **build, "source": "kernel"})
    dxp_t, dgates_t, h_prev_t, db, *dbx = scan_call(
        functools.partial(_bwd_step, cell, route.variant, sums_pair), route,
        reverse=reverse, hidden=h, gates=gates,
        rows=([(xp_t, at_bptt), (mask_t, at_bptt)]
              + [(s, at_prev) for s in seqs] + [(dy_t, at_bptt)]
              + ([(other_dxp_t, at_bptt)] if sums_pair else [])),
        weights=[w, bh2],
        outs=[(gates * h, dxp_dtype, at_bptt),
              (gates * h, jnp.float32, at_bptt),
              (h, jnp.float32, at_bptt, flat)],
        whole_outs=[(db_rows, gates * h)] * (1 + sums_pair),
        scratch=lambda cols: [h] * n + (
            [h, cols, cols] if streamed else []),
        interpret=interpret,
        more_facts={"sum": "pair"} if sums_pair else None)
    # One big MXU contraction instead of a per-step VMEM
    # accumulator, from float32 operands whatever the dot type: at
    # dot_dtype=bf16 the ORACLE's dW is the noisy one (it rounds
    # h_prev to bf16 in its per-step outer products, rel err ~3e-2
    # vs f32 truth; tests/test_pallas.py
    # test_gru_bf16_dw_closer_to_truth_than_oracle) while this
    # contraction stays ~2e-3, which is the recurrence's own bf16
    # noise and not the contraction's (recurrent_dw).
    dw_h = recurrent_dw(h_prev_t, dgates_t.reshape(
        h_prev_t.shape[:-1] + (gates * h,)), dot)
    return (dxp_t, dw_h.astype(w_h.dtype),
            jnp.sum(db, axis=0).astype(b_h.dtype),
            jnp.sum(dbx[0], axis=0) if sums_pair else None)


def _mask_cotangent(mask_t):
    return jnp.zeros_like(mask_t[..., 0]).swapaxes(0, 1)


def scan_vjp(cell: ScanCell):
    """The ``custom_vjp`` pair of a gated cell's
    ``f(xproj, mask, w_h, b_h, reverse, interpret, dot_dtype)``: the
    taped forward call, and :func:`_scan_backward`'s one call, whose
    float32 ``dxp`` goes back batch-major as it is. A layer with two
    directions does not come here (jax would add the two functions'
    ``dxp`` in a pass of its own): :func:`scan_pair_vjp`."""

    @jax.named_scope("rnn_scan")
    def fwd(xproj, mask, w_h, b_h, reverse, interpret, dot_dtype):
        seqs, xp_t, mask_t = scan_forward(
            cell, xproj, mask, w_h, b_h, reverse=reverse,
            interpret=interpret, dot_dtype=dot_dtype, tape=True)
        return jnp.moveaxis(seqs[0], 0, 1), (xp_t, mask_t, w_h, b_h, *seqs)

    @jax.named_scope("rnn_scan")
    def bwd(reverse, interpret, dot_dtype, residuals, dy):
        dy_t = jnp.moveaxis(dy.astype(jnp.float32), 1, 0)  # [T, B, H]
        dxp_t, dw_h, db_h, _ = _scan_backward(
            cell, residuals, dy_t, reverse=reverse, interpret=interpret,
            dot_dtype=dot_dtype)
        return (jnp.moveaxis(dxp_t, 0, 1),  # [B, T, G*H]
                _mask_cotangent(residuals[1]), dw_h, db_h)

    return fwd, bwd


def add_proj_bias(product, bias):
    """The input projection's rows ``xproj`` from its matmul's
    ``product [B, T, G*H]`` and its bias ``[G*H]``, in the product's
    type: the add ``flax.linen.Dense`` makes after its matmul, under
    the projection's name (``obs/layers.py`` reads a device
    operation's layer from its scopes)."""
    with jax.named_scope("wx"):
        return product + jnp.reshape(bias.astype(product.dtype),
                                     (1,) * (product.ndim - 1) + (-1,))


def scan_pair_vjp(cell: ScanCell):
    """A gated cell's two directions over the SAME input projection as
    ONE function under its own ``custom_vjp``: ``f(product, mask, b_x,
    w_f, b_f, w_b, b_b, interpret=False, dot_dtype=None) -> ys_f + ys_b
    [B, T, H]``, ``product [B, T, G*H]`` the projection's matmul and
    ``b_x [G*H]`` its bias, which the function adds itself
    (:func:`add_proj_bias`: ``xproj``, the program ``flax.linen.Dense``
    states) so that the bias' cotangent has somewhere to come from.
    Forward it is then the two calls a one-direction layer makes, one a
    direction (same kernels, facts and residuals). Backward, its two
    calls make the input projection's gradient between them, in the
    form the projection's backward reads. The first (the forward
    direction's) is a one-direction layer's call and hands its float32
    ``dxp_t [T, b, G*H]`` to the second; the second reads those rows
    under its own time map (both maps index frames, so the same rows
    meet although the two calls run through time in opposite orders),
    adds its own in float32 and writes the ONE sum rounded to
    ``xproj``'s dtype, with the float32 sum's column sums beside it:
    ``product``'s cotangent and ``b_x``'s. That is what the program
    states with two functions (``add_any`` of the two float32
    cotangents, the cast to the projection's dtype, a ``reduce_sum``
    of that for the bias), without XLA's passes over ``[T, b, G*H]``
    for the sum, for the cast and for the column sums; and the bias
    gradient is the sum of the float32 values, not of the rounded
    ones. Each direction's ``dgates``, ``h_prev``, ``db``, ``dW_h`` and
    ``db_h`` are a one-direction layer's bit for bit."""

    @jax.named_scope("rnn_scan")
    def both(product, mask, b_x, w_f, b_f, w_b, b_b, tape, **kw):
        xproj = add_proj_bias(product, b_x)
        seqs_f, xp_t, mask_t = scan_forward(
            cell, xproj, mask, w_f, b_f, reverse=False, tape=tape, **kw)
        seqs_b, _, _ = scan_forward(
            cell, xproj, mask, w_b, b_b, reverse=True, tape=tape, **kw)
        ys = jnp.moveaxis(seqs_f[0], 0, 1) + jnp.moveaxis(seqs_b[0], 0, 1)
        return ys, (xp_t, mask_t, b_x, (w_f, b_f, *seqs_f),
                    (w_b, b_b, *seqs_b))

    @functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
    def pair(product, mask, b_x, w_f, b_f, w_b, b_b, interpret=False,
             dot_dtype=None):
        return both(product, mask, b_x, w_f, b_f, w_b, b_b, False,
                    interpret=interpret, dot_dtype=dot_dtype)[0]

    def fwd(product, mask, b_x, w_f, b_f, w_b, b_b, interpret, dot_dtype):
        return both(product, mask, b_x, w_f, b_f, w_b, b_b, True,
                    interpret=interpret, dot_dtype=dot_dtype)

    @jax.named_scope("rnn_scan")
    def bwd(interpret, dot_dtype, residuals, dy):
        xp_t, mask_t, b_x, fw, bw = residuals
        dy_t = jnp.moveaxis(dy.astype(jnp.float32), 1, 0)  # [T, B, H]
        kw = dict(interpret=interpret, dot_dtype=dot_dtype)
        dxp_f, dw_f, db_f, _ = _scan_backward(
            cell, (xp_t, mask_t, *fw), dy_t, reverse=False, **kw)
        # The first call's weight gradient before the second call: its
        # operands (dgates and h_prev, 766 MB at ds2_full's call) are
        # then dead while the second runs. Left free, XLA's scheduler
        # put both contractions after both calls (+0.47 GB at the
        # step's peak, by the compiler's own count). Only dxp_f waits
        # on the barrier; dw_f goes round it, so that what follows the
        # contraction fuses with it as in a one-direction layer.
        dxp_f, _ = jax.lax.optimization_barrier((dxp_f, dw_f))
        dxp_t, dw_b, db_b, db_x = _scan_backward(
            cell, (xp_t, mask_t, *bw), dy_t, reverse=True,
            other_dxp_t=dxp_f, **kw)
        # the sum as the kernel rounded it: [B, T, G*H] in xproj's dtype
        return (jnp.moveaxis(dxp_t, 0, 1), _mask_cotangent(mask_t),
                db_x.astype(b_x.dtype), dw_f, db_f, dw_b, db_b)

    pair.defvjp(fwd, bwd)
    return pair
