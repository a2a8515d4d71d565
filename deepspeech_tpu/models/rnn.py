"""Recurrent stack: GRU/LSTM over `lax.scan` (SURVEY.md §2 component 6).

This is the XLA reference path that replaces cuDNN's fused RNN kernels.
The TPU-first decomposition:

- The input projection ``x @ W_x`` for ALL timesteps is hoisted out of
  the time loop into one large [B*T, D] x [D, 3H] matmul — exactly the
  shape the MXU wants, and the bulk of the FLOPs.
- Only the recurrent matmul ``h @ W_h`` stays inside ``lax.scan``.
- Bidirectional = forward scan + scan over the time-reversed sequence
  (masked so right-padding never pollutes hidden state); directions are
  summed, as in DS2, keeping output width H for all variants.

The fused Pallas cell (ops/rnn_pallas.py) implements the same
``(xproj, mask, W_h, b_h) -> outputs`` contract and is swapped in via
``ModelConfig.rnn_impl = "pallas"``; this scan version remains the
test oracle.

Gate conventions (cuDNN-style, matching flax GRUCell):
  r = sigmoid(xp_r + h W_r + b_r)
  z = sigmoid(xp_z + h W_z + b_z)
  n = tanh(xp_n + r * (h W_n + b_n))
  h' = (1 - z) * n + z * h
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..config import ModelConfig
from .layers import MaskedBatchNorm, length_mask


def _scan_steps(step, init, xs, t: int, remat_chunk: int):
    """lax.scan over ``t`` steps, optionally as a chunked double scan
    with per-chunk rematerialization.

    A plain scan's backward pass stores every step's residuals (gates,
    activations) — O(T) HBM on top of the O(T) primal outputs. With
    ``remat_chunk=k`` the time axis is split into ceil(T/k) chunks; the
    outer scan stores only chunk-boundary carries and the backward pass
    recomputes each chunk's internals from its boundary (jax.checkpoint)
    — residual memory drops to O(k), costing one extra forward of the
    recurrence. The math is the identical step sequence, so outputs are
    bit-equal to the plain scan. Padding steps carry zero masks, which
    the step functions treat as identity.
    """
    if remat_chunk <= 0 or t <= remat_chunk:
        return jax.lax.scan(step, init, xs)
    k = remat_chunk
    n = -(-t // k)
    pad = n * k - t
    if pad:
        xs = jax.tree.map(
            lambda a: jnp.concatenate(
                [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]), xs)
    xs = jax.tree.map(lambda a: a.reshape((n, k) + a.shape[1:]), xs)

    @jax.checkpoint
    def chunk(carry, xc):
        return jax.lax.scan(step, carry, xc)

    final, ys = jax.lax.scan(chunk, init, xs)  # ys leaves [n, k, ...]
    ys = jax.tree.map(
        lambda a: a.reshape((n * k,) + a.shape[2:])[:t], ys)
    return final, ys


def gru_scan(xproj: jnp.ndarray, mask: jnp.ndarray, w_h: jnp.ndarray,
             b_h: jnp.ndarray, reverse: bool = False,
             dot_dtype: jnp.dtype | None = None,
             h0: jnp.ndarray | None = None,
             return_final: bool = False,
             remat_chunk: int = 0
             ) -> jnp.ndarray | Tuple[jnp.ndarray, jnp.ndarray]:
    """Run the GRU recurrence. xproj [B, T, 3H] already includes b_x.

    mask [B, T] (1=valid). Returns hidden outputs [B, T, H] (float32),
    or ``(outputs, final_carry [B, H])`` when ``return_final=True``.
    ``dot_dtype`` is the MXU input precision for the recurrent matmul
    (cuDNN-style mixed precision: bf16 operands, f32 accumulate/carry);
    None keeps full float32. ``h0``/``return_final`` support chunked
    streaming inference (deepspeech_tpu/streaming.py): pass the carry
    from the previous chunk, get the carry for the next.
    ``remat_chunk`` > 0 bounds backward-pass residual memory to that
    many steps via chunked rematerialization (_scan_steps).
    """
    b, t, h3 = xproj.shape
    h = h3 // 3
    xproj = xproj.astype(jnp.float32)
    if reverse:
        if return_final or h0 is not None:
            raise ValueError("streaming carry only supports forward scans")
        xproj = xproj[:, ::-1]
        mask = mask[:, ::-1]
    if dot_dtype is not None:
        w_h = w_h.astype(dot_dtype)  # cast once, outside the time loop
    xs = (jnp.moveaxis(xproj, 1, 0), jnp.moveaxis(mask, 1, 0))
    if h0 is None:
        h0 = jnp.zeros((b, h), jnp.float32)

    def step(hprev, xt):
        xp, m = xt
        hin = hprev if dot_dtype is None else hprev.astype(dot_dtype)
        gates = jnp.dot(hin, w_h, preferred_element_type=jnp.float32) + b_h
        g_r, g_z, g_n = jnp.split(gates, 3, axis=-1)
        xp_r, xp_z, xp_n = jnp.split(xp, 3, axis=-1)
        r = jax.nn.sigmoid(xp_r + g_r)
        z = jax.nn.sigmoid(xp_z + g_z)
        n = jnp.tanh(xp_n + r * g_n)
        hnew = (1.0 - z) * n + z * hprev
        hnew = m[:, None] * hnew + (1.0 - m[:, None]) * hprev
        return hnew, hnew

    h_final, ys = _scan_steps(step, h0.astype(jnp.float32), xs, t,
                              remat_chunk)
    ys = jnp.moveaxis(ys, 0, 1)  # [B, T, H]
    if reverse:
        ys = ys[:, ::-1]
    if return_final:
        return ys, h_final
    return ys


def lstm_scan(xproj: jnp.ndarray, mask: jnp.ndarray, w_h: jnp.ndarray,
              b_h: jnp.ndarray, reverse: bool = False,
              dot_dtype: jnp.dtype | None = None,
              remat_chunk: int = 0,
              hc0: Tuple[jnp.ndarray, jnp.ndarray] | None = None,
              return_final: bool = False):
    """LSTM recurrence; xproj [B, T, 4H] (i, f, g, o order).

    ``hc0`` (h, c) / ``return_final`` mirror gru_scan's streaming-carry
    contract (forward scans only) — used by the sequence-parallel relay
    (parallel/seqpar.py) to hand both states across time shards.
    """
    b, t, h4 = xproj.shape
    h = h4 // 4
    xproj = xproj.astype(jnp.float32)
    if reverse:
        if return_final or hc0 is not None:
            raise ValueError("streaming carry only supports forward scans")
        xproj = xproj[:, ::-1]
        mask = mask[:, ::-1]
    if dot_dtype is not None:
        w_h = w_h.astype(dot_dtype)
    xs = (jnp.moveaxis(xproj, 1, 0), jnp.moveaxis(mask, 1, 0))
    init = ((jnp.zeros((b, h), jnp.float32),
             jnp.zeros((b, h), jnp.float32)) if hc0 is None
            else (hc0[0].astype(jnp.float32), hc0[1].astype(jnp.float32)))

    def step(carry, xt):
        hprev, cprev = carry
        xp, m = xt
        hin = hprev if dot_dtype is None else hprev.astype(dot_dtype)
        gates = xp + jnp.dot(hin, w_h,
                             preferred_element_type=jnp.float32) + b_h
        gi, gf, gg, go = jnp.split(gates, 4, axis=-1)
        i = jax.nn.sigmoid(gi)
        f = jax.nn.sigmoid(gf + 1.0)  # forget-gate bias init trick
        g = jnp.tanh(gg)
        o = jax.nn.sigmoid(go)
        cnew = f * cprev + i * g
        hnew = o * jnp.tanh(cnew)
        mm = m[:, None]
        hnew = mm * hnew + (1.0 - mm) * hprev
        cnew = mm * cnew + (1.0 - mm) * cprev
        return (hnew, cnew), hnew

    final, ys = _scan_steps(step, init, xs, t, remat_chunk)
    ys = jnp.moveaxis(ys, 0, 1)
    if reverse:
        ys = ys[:, ::-1]
    if return_final:
        return ys, final
    return ys


LN_EPS = 1e-5


def gate_layer_norm(a: jnp.ndarray, scale: jnp.ndarray,
                    bias: jnp.ndarray) -> jnp.ndarray:
    """Layer normalisation of each of the four gate pre-activations
    on its own: a [B, 4H] -> [B, 4H], statistics over the H units of
    one gate, learned gain and bias [4H]."""
    b, h4 = a.shape
    g = a.reshape(b, 4, h4 // 4)
    mu = jnp.mean(g, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(g - mu), axis=-1, keepdims=True)
    g = (g - mu) * jax.lax.rsqrt(var + LN_EPS)
    return g.reshape(b, h4) * scale + bias


def lstmp_scan(xproj: jnp.ndarray, mask: jnp.ndarray, w_r: jnp.ndarray,
               w_p: jnp.ndarray, ln_scale: jnp.ndarray | None = None,
               ln_bias: jnp.ndarray | None = None,
               dot_dtype: jnp.dtype | None = None,
               remat_chunk: int = 0,
               cr0: Tuple[jnp.ndarray, jnp.ndarray] | None = None,
               return_final: bool = False):
    """LSTM with a recurrent projection (Sak et al., arXiv:1402.1128,
    no peepholes) and, optionally, layer normalisation of the gates.

      a = xproj_t + r_{t-1} W_r          [B, 4H] (i, f, g, o; xproj
                                         includes the input bias)
      i, f, g, o = LN_k(a_k)             per gate, if ln_scale is given
      c = sig(f + 1) c + sig(i) tanh(g)  (+1: the forget-gate bias of
                                         ``lstm_scan``)
      m = sig(o) tanh(c);  r = m W_p     [B, P]

    xproj [B, T, 4H], mask [B, T] (masked frames carry (c, r)
    through), w_r [P, 4H], w_p [H, P]. Returns r [B, T, P] float32, or
    ``(r, (c_final, r_final))`` with ``return_final``; ``cr0`` is the
    carried (c [B, H], r [B, P]) of a one-step decode.
    """
    b, t, h4 = xproj.shape
    h, p = h4 // 4, w_p.shape[1]
    if dot_dtype is not None:
        w_r, w_p = w_r.astype(dot_dtype), w_p.astype(dot_dtype)
    xs = (jnp.moveaxis(xproj, 1, 0), jnp.moveaxis(mask, 1, 0))
    init = ((jnp.zeros((b, h), jnp.float32),
             jnp.zeros((b, p), jnp.float32)) if cr0 is None
            else (cr0[0].astype(jnp.float32), cr0[1].astype(jnp.float32)))

    def step(carry, xt):
        cprev, rprev = carry
        xp, m = xt
        a = xp.astype(jnp.float32) + jnp.dot(
            rprev.astype(w_r.dtype), w_r,
            preferred_element_type=jnp.float32)
        if ln_scale is not None:
            a = gate_layer_norm(a, ln_scale, ln_bias)
        gi, gf, gg, go = jnp.split(a, 4, axis=-1)
        cnew = (jax.nn.sigmoid(gf + 1.0) * cprev
                + jax.nn.sigmoid(gi) * jnp.tanh(gg))
        mout = jax.nn.sigmoid(go) * jnp.tanh(cnew)
        rnew = jnp.dot(mout.astype(w_p.dtype), w_p,
                       preferred_element_type=jnp.float32)
        mm = m[:, None]
        cnew = mm * cnew + (1.0 - mm) * cprev
        rnew = mm * rnew + (1.0 - mm) * rprev
        return (cnew, rnew), rnew

    final, ys = _scan_steps(step, init, xs, t, remat_chunk)
    ys = jnp.moveaxis(ys, 0, 1)
    if return_final:
        return ys, final
    return ys


# Backward of the XLA lstmp scan: a plain scan tapes every step's four
# gates and cell state ([B, 4H] + [B, H] float32 a step, 13 GB over the
# 2838 encoder steps of rnnt_he2019 at b=64), so the scan is cut into
# chunks whose internals are recomputed from their boundary carries.
LSTMP_REMAT_CHUNK = 32


def layer_scan_route(cfg: ModelConfig, rows=None, *, mesh=None,
                     cell=None, hidden=None, **facts):
    """``ops/scan_pallas.scan_route`` for one recurrent layer of this
    model: which kernel and build run it, or that the XLA scan does.
    From the configuration come the cell type, the resolved
    ``rnn_impl``, the widths and the dot type; from the call ``rows``
    (the batch's, divided over ``mesh``'s data axis here) and whatever
    else it observes (``int8``, ``carry``, ``directions``)."""
    from ..ops.scan_pallas import scan_route
    from ..parallel.mesh import DATA_AXIS
    from ..utils.impl import resolve_impl

    if rows is not None and mesh is not None:
        rows //= mesh.shape[DATA_AXIS]
    facts.setdefault("proj", cfg.rnn_proj)
    return scan_route(
        cell or cfg.rnn_type, resolve_impl(cfg.rnn_impl, oracle="xla"),
        rows=rows, hidden=hidden or cfg.rnn_hidden,
        dot_bytes=jnp.dtype(cfg.dtype).itemsize, **facts)


# The forward kernels whose pair function is the kernel's two calls
# (``ops/scan_pallas.scan_pair_vjp``): it takes the input projection's
# bias apart from its matmul, before the weights.
_PAIR_SUMS = ("gru_scan_fwd", "lstm_scan_fwd")


def _scan_kernel(kernel: str, pair: bool = False):
    """The function of ``ops/`` that builds the forward kernel a route
    names, called as ``f(xproj, mask, *weights, [reverse,] interpret,
    dot_dtype)``; with ``pair`` the one that runs a layer's two
    directions where the route over both names that kernel (both
    weight sets, no ``reverse``; the kernels of :data:`_PAIR_SUMS`:
    ``f(product, mask, bias, *weights, ...)``), or None where there is
    none."""
    from ..ops import lstm_pallas, rnn_pallas

    if pair:
        return {"bigru_scan_fwd": rnn_pallas.bigru_scan_pallas,
                "gru_scan_fwd": rnn_pallas.gru_scan_pair_pallas,
                "lstm_scan_fwd": lstm_pallas.lstm_scan_pair_pallas,
                }.get(kernel)
    return {"gru_scan_fwd": rnn_pallas.gru_scan_pallas,
            "gru_scan_q_fwd": rnn_pallas.gru_scan_pallas_q,
            "lstm_scan_fwd": lstm_pallas.lstm_scan_pallas,
            "lstm_scan_q_fwd": lstm_pallas.lstm_scan_pallas_q,
            "lstmp_scan_fwd": lstm_pallas.lstmp_scan_pallas}[kernel]


def _run_kernel(cfg: ModelConfig, kernel: str, mesh, xproj, mask, *weights,
                reverse=None, pair: bool = False):
    """The routed kernel over this layer's operands (``pair``: over
    both directions' weights, :func:`_scan_kernel`). On a multi-device
    mesh it partitions over the data axis via shard_map (batch args
    sharded, weights replicated); single-device meshes pass through
    untouched."""
    from ..parallel.mesh import shard_batchwise
    from ..utils.impl import interpret_default

    tail = (() if reverse is None else (reverse,)) + (
        interpret_default(), _pallas_dot_dtype(jnp.dtype(cfg.dtype)))
    fn = _scan_kernel(kernel, pair)
    return shard_batchwise(lambda xp, m, *w: fn(xp, m, *w, *tail), mesh,
                           n_sharded=2)(xproj, mask, *weights)


def _run_lstmp(cfg: ModelConfig, xproj, mask, w_r, w_p, ln_scale,
               ln_bias, mesh=None):
    """A whole-sequence LSTM-with-projection recurrence from a zero
    carry: the fused Pallas kernels where the route names them
    (``rnn_impl`` resolves to them, sublane-aligned local batch rows,
    weights within the kernels' VMEM limit), else the XLA scan."""
    dtype = jnp.dtype(cfg.dtype)
    h, p = w_p.shape
    route = layer_scan_route(cfg, xproj.shape[0], mesh=mesh, cell="lstmp",
                             hidden=h, proj=p)
    if route.kernel is not None:
        return _run_kernel(cfg, route.kernel, mesh, xproj, mask, w_r, w_p,
                           ln_scale, ln_bias)
    return lstmp_scan(
        xproj, mask, w_r, w_p, ln_scale, ln_bias,
        dot_dtype=None if dtype == jnp.float32 else dtype,
        remat_chunk=cfg.rnn_remat_chunk or LSTMP_REMAT_CHUNK)


class LSTMPLayer(nn.Module):
    """One unidirectional LSTM-with-projection layer: the hoisted input
    projection (one matmul over all frames) and the recurrence.
    ``hidden`` cells, ``proj``-wide output. With ``cr0`` (the decoders'
    carried one-step path) it runs the XLA scan and returns the final
    carry too."""

    cfg: ModelConfig
    hidden: int
    proj: int
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, mask: jnp.ndarray, cr0=None,
                 return_final: bool = False):
        cfg, h, p = self.cfg, self.hidden, self.proj
        dtype = jnp.dtype(cfg.dtype)
        xproj = nn.Dense(4 * h, dtype=dtype, name="wx")(x.astype(dtype))
        w_r = self.param("wr", nn.initializers.orthogonal(),
                         (p, 4 * h), jnp.float32)
        w_p = self.param("wp", nn.initializers.lecun_normal(),
                         (h, p), jnp.float32)
        ln_scale = ln_bias = None
        if cfg.rnn_layer_norm:
            ln_scale = self.param("ln_scale", nn.initializers.ones,
                                  (4 * h,), jnp.float32)
            ln_bias = self.param("ln_bias", nn.initializers.zeros,
                                 (4 * h,), jnp.float32)
        if cr0 is None and not return_final:
            return _run_lstmp(cfg, xproj, mask, w_r, w_p, ln_scale,
                              ln_bias, mesh=self.mesh)
        return lstmp_scan(
            xproj, mask, w_r, w_p, ln_scale, ln_bias,
            dot_dtype=None if dtype == jnp.float32 else dtype,
            cr0=cr0, return_final=return_final)


def stack_frames(x: jnp.ndarray, lens: jnp.ndarray, k: int):
    """Concatenate every ``k`` adjacent frames into one: x [B, T, D]
    (zero past ``lens``) -> [B, ceil(T/k), k*D], lens -> ceil(lens/k)."""
    if k == 1:
        return x, lens
    b, t, d = x.shape
    n = -(-t // k)
    x = jnp.pad(x, [(0, 0), (0, n * k - t), (0, 0)])
    return x.reshape(b, n, k * d), -(-lens // k)


class LSTMPEncoder(nn.Module):
    """The encoder of He et al. 2019 (arXiv:1811.06621): stacked
    feature frames, ``rnn_layers`` LSTM-with-projection layers, and one
    time reduction after layer ``time_reduction_layer``."""

    cfg: ModelConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, features: jnp.ndarray, feat_lens: jnp.ndarray):
        cfg = self.cfg
        x, lens = stack_frames(features, feat_lens, cfg.frame_stack)
        for i in range(cfg.rnn_layers):
            mask = length_mask(lens, x.shape[1])
            x = LSTMPLayer(cfg, cfg.rnn_hidden, cfg.rnn_proj, self.mesh,
                           name=f"lstmp{i}")(x, mask)
            x = (x * mask[:, :, None]).astype(jnp.dtype(cfg.dtype))
            if i + 1 == cfg.time_reduction_layer:
                x, lens = stack_frames(x, lens, cfg.time_reduction)
        return x, lens


def _pallas_dot_dtype(dtype) -> "str | None":
    """Single derivation of the Pallas cells' MXU operand precision
    from the model compute dtype (mirrors the oracle's mixed precision:
    reduced operands, f32 accumulate/carry)."""
    return None if dtype == jnp.float32 else str(dtype)


def _is_qdict(w) -> bool:
    """Weight-only int8 leaf from utils/quantize.py left IN the param
    tree (infer's serving path)."""
    from ..utils.quantize import is_qleaf

    return is_qleaf(w)


def _run_direction(cfg: ModelConfig, xproj, mask, w_h, b_h, reverse,
                   mesh=None):
    """One direction of one layer through what the route names: a fused
    cell at every H (resident, copied once or streamed; int8 weights
    straight into the q kernels, so the quantized matrix IS what moves,
    the recurrent bandwidth win PTQ exists for), else the XLA scan."""
    dtype = jnp.dtype(cfg.dtype)
    quantized = _is_qdict(w_h)
    route = layer_scan_route(cfg, xproj.shape[0], mesh=mesh, int8=quantized,
                             xproj_bytes=xproj.dtype.itemsize)
    if route.kernel is not None:
        weights = (w_h["q"], w_h["scale"]) if quantized else (w_h,)
        return _run_kernel(cfg, route.kernel, mesh, xproj, mask, *weights,
                           b_h, reverse=reverse)
    if quantized:
        # XLA impl: dequantize on the fly — storage win only, same math.
        w_h = w_h["q"].astype(jnp.float32) * w_h["scale"]
    scan = gru_scan if cfg.rnn_type == "gru" else lstm_scan
    dot_dtype = None if dtype == jnp.float32 else dtype
    return scan(xproj, mask, w_h, b_h, reverse=reverse, dot_dtype=dot_dtype,
                remat_chunk=cfg.rnn_remat_chunk)


def _run_stack_dirs(cfg: ModelConfig, product, bias, mask, params,
                    mesh=None):
    """Run the direction set of one layer over its input projection,
    handed over as :class:`InputProjection` leaves it (``xproj =
    product + bias``); ``params[rev] = (w_h, b_h)``.

    Two float directions run as ONE function of the layer's ``xproj``,
    from what the route names for them. Where both weight sets fit
    VMEM together, ONE fused kernel (ops/rnn_pallas.bigru_scan_pallas):
    the independent per-step matmuls of the two directions hide each
    other's latency instead of serializing as two kernels. Where the
    route names a one-direction kernel (a matrix copied once or
    streamed: ds2_full), that kernel's pair function
    (ops/scan_pallas.scan_pair_vjp), under ONE ``shard_batchwise``:
    the forward calls are the two a direction loop makes, and backward
    the forward direction's call hands its float32 ``dxp`` to the
    reverse direction's, which writes the projection's gradient as its
    backward reads it (the two's sum in ``xproj``'s type and, for the
    bias, that sum's column sums), so no pass outside the kernels
    adds, casts or reduces a ``[B, T, G*H]`` cotangent. That function
    alone takes ``product`` and ``bias`` apart, the bias replicated
    like the recurrent weights; everywhere else the bias is added
    here, as ``nn.Dense`` added it. One direction, int8 leaves and the
    XLA scan compose per direction.
    """
    from ..ops.scan_pallas import add_proj_bias

    if len(params) == 2 and not any(_is_qdict(w) for w, _ in params.values()):
        route = layer_scan_route(cfg, product.shape[0], mesh=mesh,
                                 directions=2,
                                 xproj_bytes=product.dtype.itemsize)
        if _scan_kernel(route.kernel, pair=True) is not None:
            operands = ((product, mask, bias) if route.kernel in _PAIR_SUMS
                        else (add_proj_bias(product, bias), mask))
            return _run_kernel(cfg, route.kernel, mesh, *operands,
                               *params[False], *params[True], pair=True)
    xproj = add_proj_bias(product, bias)
    out = None
    for rev, (w_h, b_h) in params.items():
        ys = _run_direction(cfg, xproj, mask, w_h, b_h, rev, mesh=mesh)
        out = ys if out is None else out + ys
    return out


class InputProjection(nn.Module):
    """The hoisted input projection with its bias handed back apart:
    ``nn.Dense``'s parameters (``kernel`` and ``bias``: its names,
    shapes, float32 and initialisers, so a tree made by either loads
    in the other) and its matmul in ``dtype``, returned as ``(product
    [..., features], bias [features])`` for the caller to add
    (``ops/scan_pallas.add_proj_bias``, the add ``nn.Dense`` makes) or
    to hand to a function that adds it under its own VJP."""

    features: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        kernel = self.param("kernel", nn.linear.default_kernel_init,
                            (x.shape[-1], self.features), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,), jnp.float32)
        product = jax.lax.dot_general(
            x.astype(self.dtype), kernel.astype(self.dtype),
            (((x.ndim - 1,), (0,)), ((), ())))
        return product, bias


class RNNLayer(nn.Module):
    """One (bi)directional recurrent layer with optional sequence BN."""

    cfg: ModelConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, lens: jnp.ndarray,
                 train: bool) -> jnp.ndarray:
        cfg = self.cfg
        n_gates = 3 if cfg.rnn_type == "gru" else 4
        h = cfg.rnn_hidden
        mask = length_mask(lens, x.shape[1])
        if cfg.rnn_batch_norm:
            x = MaskedBatchNorm(name="bn")(x, mask, train)
        dtype = jnp.dtype(cfg.dtype)
        # Hoisted input projection: one big MXU matmul over all frames.
        product, bias = InputProjection(n_gates * h, dtype, name="wx")(
            x.astype(dtype))

        dirs = [False, True] if cfg.bidirectional else [False]
        params = {}
        for rev in dirs:
            suffix = "bw" if rev else "fw"
            params[rev] = (
                self.param(f"wh_{suffix}", nn.initializers.orthogonal(),
                           (h, n_gates * h), jnp.float32),
                self.param(f"bh_{suffix}", nn.initializers.zeros,
                           (n_gates * h,), jnp.float32))

        out = _run_stack_dirs(cfg, product, bias, mask, params,
                              mesh=self.mesh)
        out = out * mask[:, :, None]
        return out.astype(dtype)


class RNNStack(nn.Module):
    cfg: ModelConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, lens: jnp.ndarray,
                 train: bool) -> jnp.ndarray:
        for i in range(self.cfg.rnn_layers):
            x = RNNLayer(self.cfg, mesh=self.mesh,
                         name=f"rnn{i}")(x, lens, train)
        return x
