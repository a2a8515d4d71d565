"""Weight-only int8 post-training quantization for inference.

What it buys today: 4x (vs f32) weight STORAGE — device memory
footprint and checkpoint-to-device transfer — with no calibration
data: kernels are stored int8 + a per-output-channel scale and
dequantized inside the jitted forward. For the one-shot consumers
(conv kernels, the hoisted input projections, the vocab head) XLA
fuses the convert into the consuming matmul, so those weights ride
HBM as int8 too.

It also buys the per-TIMESTEP recurrent-weight bandwidth on the
Pallas serving path. Recurrent matrices kept int8 by
``keep_recurrent_q`` feed the fused q kernels directly, in two
regimes: H that fits the 1-byte residency budget (GRU up to H=1869,
LSTM to H=1619) sits RESIDENT in VMEM — zero per-step weight traffic
— and larger H (the flagship LSTM H=1760, GRU past 1869) STREAMS s8
column tiles through the ``blocked_q`` build (ops/scan_pallas.py's
streamed step over int8 blocks), dequantizing in VMEM, so the dominant per-step HBM stream is the quantized bytes:
4× less than f32, with no fp working copy materialized anywhere.
What still pays full-precision stream bytes: the XLA-impl fallback
(``gru_scan`` dequantizes outside the scan) and the chunked streaming
engine's carried-state kernel, which is resident-only.

What quantizes: every matmul/conv kernel and the recurrent matrices
(path suffix in _QUANT_SUFFIXES). What stays f32: biases, BN
scale/bias and running stats (tiny, accuracy-critical), and anything
1-D. Symmetric absmax per OUTPUT channel (last dim), which keeps the
per-channel dynamic range tight for the gate-blocked [H, 3H/4H]
recurrent layouts.

Accuracy: exercised end-to-end by tests/test_quantize.py and the
trained-checkpoint decode drive (WER/CER 0.0 on the rehearsal corpus,
BASELINE.md). Beyond the reference's surface (no quantization path
exists there).
"""

from __future__ import annotations

import re
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Kernel-bearing leaves: flax Dense/Conv kernels, the recurrent
# matrices, and the stacked pipelined variants.
_QUANT_SUFFIXES = re.compile(
    r"(kernel|wh_fw|wh_bw|wx_kernel)$")

# Pipeline-stacked RNN leaves ([L, d, G]: one leading layer axis over
# per-layer matrices, models/pipe_stack.py). These get per-(layer,
# output-channel) scales — sharing one channel scale across L layers
# would let the widest layer coarsen every other layer's quantization
# grid (ADVICE r3 #2).
_STACKED_SUFFIXES = re.compile(r"(wh_fw|wh_bw|wx_kernel)$")

_INT8_MAX = 127.0

# Module-wide PTQ invocation count. Quantization is meant to run
# exactly once per replica/engine at init — never per request — and
# tests/test_quantize.py's two-tier scenario asserts that by reading this before/after
# building the pool and after serving traffic.
QUANTIZE_CALLS = 0


def _keyname(k) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def _leaf_paths(tree):
    return [("/".join(_keyname(k) for k in path), leaf) for path, leaf in
            jax.tree_util.tree_leaves_with_path(tree)]


def should_quantize(path: str, leaf) -> bool:
    return (_QUANT_SUFFIXES.search(path) is not None
            and getattr(leaf, "ndim", 0) >= 2)


def quantize_params(params) -> Tuple[Any, Dict[str, int]]:
    """params -> (qtree, report).

    qtree mirrors ``params`` except that each quantized leaf becomes a
    ``{"q": int8 [..., C], "scale": f32 [C]}`` dict (scale per output
    channel = last dim; pipeline-stacked [L, d, C] leaves get
    per-(layer, channel) scales of shape [L, 1, C]). ``report`` counts
    quantized/kept leaves and byte totals. Dequantization is
    ``q * scale`` (symmetric, zero-point free — weights are
    zero-centered in practice and symmetric keeps the matmul fusable).
    """
    global QUANTIZE_CALLS
    QUANTIZE_CALLS += 1
    report = {"quantized": 0, "kept": 0, "bytes_before": 0,
              "bytes_after": 0}

    def one(path_tuple, leaf):
        path = "/".join(_keyname(k) for k in path_tuple)
        arr = np.asarray(leaf)
        report["bytes_before"] += arr.nbytes
        if not should_quantize(path, arr):
            report["kept"] += 1
            report["bytes_after"] += arr.nbytes
            return leaf
        if arr.ndim == 3 and _STACKED_SUFFIXES.search(path):
            # [L, d, C] pipeline stack: scale [L, 1, C] (broadcasts in
            # both the quantize below and dequantize_params' q*scale).
            absmax = np.max(np.abs(arr), axis=1, keepdims=True)
        else:
            absmax = np.max(np.abs(arr.reshape(-1, arr.shape[-1])),
                            axis=0)
        scale = (absmax / _INT8_MAX).astype(np.float32)
        scale = np.where(scale == 0.0, 1.0, scale)
        q = np.clip(np.rint(arr / scale), -127, 127).astype(np.int8)
        report["quantized"] += 1
        report["bytes_after"] += q.nbytes + scale.nbytes
        return {"q": jnp.asarray(q), "scale": jnp.asarray(scale)}

    qtree = jax.tree_util.tree_map_with_path(one, params)
    return qtree, report


def is_qleaf(x) -> bool:
    """A weight-only int8 leaf: mapping with exactly q + scale (flax
    may hand it back as a FrozenDict, hence Mapping). THE single
    predicate — consumers (models/rnn, streaming) import it rather
    than re-deriving the layout."""
    from collections.abc import Mapping

    return isinstance(x, Mapping) and set(x) == {"q", "scale"}


_is_qleaf = is_qleaf  # internal alias


def keep_recurrent_q(model_cfg, streaming: bool = False) -> \
        "callable | None":
    """The int8 serving regimes, in ONE place: returns the ``keep``
    predicate for :func:`dequantize_params` when the engine should
    thread recurrent matrices int8 into the fused q kernels
    (ops/rnn_pallas.gru_scan_pallas_q /
    ops/lstm_pallas.lstm_scan_pallas_q), else None (dequant at entry).

    Conditions: the route (ops/scan_pallas.scan_route) names a q
    kernel for the int8 matrix — the resolved rnn impl is pallas and
    the cell has one (GRU or LSTM) — and the tree is non-pipelined
    (models/pipe_stack threads wh_* straight into gru_scan with no
    qdict handling). Every H qualifies on the batch path — resident or
    s8 blocked streaming — but ``streaming=True`` (the chunked engine,
    which re-enters the kernel with a carried ``h0``) additionally
    requires the 1-byte residency budget: the carried-state form is
    resident-only.
    """
    from ..models.rnn import layer_scan_route

    if (model_cfg.rnn_type in ("gru", "lstm")
            and model_cfg.pipeline_stages == 1
            and layer_scan_route(model_cfg, int8=True,
                                 carry=streaming).kernel is not None):
        return lambda path: path.endswith(("wh_fw", "wh_bw"))
    return None


def kernel_regime(model_cfg, quantized: bool,
                  streaming: bool = False) -> str:
    """Which recurrent-kernel regime a replica's forward runs in:
    ``"resident-q"`` (int8 weights VMEM-resident), ``"blocked-q"``
    (s8 column streaming with in-VMEM dequant), or ``"fp"`` (full-
    precision kernels / dequant-at-entry). Recorded per replica by the
    two-tier serving scenario so throughput deltas can be attributed to the
    kernel path."""
    from ..models.rnn import layer_scan_route

    if not quantized or keep_recurrent_q(model_cfg,
                                         streaming=streaming) is None:
        return "fp"
    return layer_scan_route(model_cfg, int8=True,
                            carry=streaming).variant.replace("_", "-")


def dequantize_params(qtree, dtype=jnp.float32, keep=None):
    """qtree -> params with each quantized leaf reconstructed as
    ``q * scale``. Call INSIDE the jitted forward: the int8 arrays are
    the jit inputs (what lives in / streams from HBM), the converts
    fuse into the consumers.

    ``keep``: optional ``predicate(path_str) -> bool``; matching leaves
    stay ``{"q", "scale"}`` for consumers that dequantize in-kernel
    (models/rnn reads them into ops/rnn_pallas.gru_scan_pallas_q, the
    per-timestep recurrent-bandwidth win).
    """
    if keep is None:
        return jax.tree.map(
            lambda x: (x["q"].astype(dtype) * x["scale"].astype(dtype)
                       if _is_qleaf(x) else x),
            qtree, is_leaf=_is_qleaf)

    def one(path_tuple, x):
        if not _is_qleaf(x):
            return x
        if keep("/".join(_keyname(k) for k in path_tuple)):
            return dict(x)
        return x["q"].astype(dtype) * x["scale"].astype(dtype)

    return jax.tree_util.tree_map_with_path(one, qtree, is_leaf=_is_qleaf)


def quantization_error(params, qtree) -> float:
    """Max relative L2 error over quantized leaves (diagnostics)."""
    deq = dequantize_params(qtree)
    errs = []
    for (path, a), (_, b) in zip(_leaf_paths(params), _leaf_paths(deq)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        denom = np.linalg.norm(a)
        if should_quantize(path, a) and denom > 0:
            errs.append(float(np.linalg.norm(a - b) / denom))
    return max(errs) if errs else 0.0
