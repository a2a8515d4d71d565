"""Device mesh + sharding rules (SURVEY.md §2 component 14).

The reference's NCCL backend disappears entirely on TPU: we define a
``jax.sharding.Mesh`` with axes ``("data", "model")``, annotate batch
and parameter shardings, and let XLA insert the gradient all-reduce
(lowered onto ICI rings; across hosts it rides DCN after
``jax.distributed.initialize``). There is no user-visible communication
backend to configure — that is the point.

- ``data``: batch-dimension data parallelism (the reference's only
  strategy; parity requirement).
- ``model``: tensor parallelism for the big vocab head / FC layers —
  not needed for DS2 parity but load-bearing for the AISHELL config
  (V ~ 4.3k) and reserved so the mesh shape is stable.
- ``pipe`` (len-3 mesh shapes only): pipeline parallelism for the
  homogeneous middle of the RNN stack (models/pipe_stack.py) — layer
  weights and their optimizer state shard over this axis, activations
  flow stage-to-stage via ``ppermute`` inside a GPipe microbatch
  schedule. Beyond the reference (DP-only); exists for models whose
  stacked RNN weights outgrow one chip's HBM.
"""

from __future__ import annotations

import functools
import logging
import re
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"

logger = logging.getLogger(__name__)


def make_mesh(shape: Tuple[int, ...] = (0, 1),
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a (data, model) or (data, pipe, model) mesh.

    ``shape[0] <= 0`` means 'all devices / product(rest)'. Two-element
    shapes build the classic 2-axis mesh (every existing call site);
    three-element shapes add the ``pipe`` axis between data and model
    for pipeline-parallel runs (TrainConfig.mesh_shape=(d, p, m)).
    """
    devices = list(devices if devices is not None else jax.devices())
    if len(shape) == 2:
        dp, rest, axes = shape[0], (shape[1],), (DATA_AXIS, MODEL_AXIS)
    elif len(shape) == 3:
        dp, rest, axes = (shape[0], (shape[1], shape[2]),
                          (DATA_AXIS, PIPE_AXIS, MODEL_AXIS))
    else:
        raise ValueError(f"mesh shape {shape} must be (data, model) or "
                         f"(data, pipe, model)")
    restn = int(np.prod(rest))
    if dp <= 0:
        if len(devices) % restn:
            raise ValueError(
                f"{len(devices)} devices not divisible by {rest}")
        dp = len(devices) // restn
    n = dp * restn
    if n > len(devices):
        raise ValueError(f"mesh {(dp,) + rest} needs {n} devices, "
                         f"have {len(devices)}")
    arr = np.asarray(devices[:n]).reshape((dp,) + rest)
    return Mesh(arr, axes)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batches shard along their leading (batch) axis over `data`."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# Parameter-name patterns -> PartitionSpec for the tensor-parallel axis.
# Everything else is replicated. Kernel shapes are [in, out]; sharding the
# vocab/out dim of the head splits the [T', H] x [H, V] matmul over MODEL
# and XLA all-gathers logits only where needed (decode/loss).
_PARAM_RULES = (
    (re.compile(r"head/kernel$"), P(None, MODEL_AXIS)),
    (re.compile(r"head/bias$"), P(MODEL_AXIS)),
    # Pipeline-parallel RNN middle (models/pipe_stack.py): every leaf is
    # stacked [n_layers, ...] and dim 0 shards over the pipe axis — each
    # stage's device stores only its own layers (and, via the matching
    # opt_state paths, only their momentum buffers).
    (re.compile(r"rnn_pipe/"), P(PIPE_AXIS)),
)


def param_spec(path: str) -> P:
    for pat, spec in _PARAM_RULES:
        if pat.search(path):
            return spec
    return P()


def param_shardings(mesh: Mesh, params,
                    zero_data_shard: bool = False
                    ) -> "jax.tree_util.PyTreeDef":
    """Pytree of NamedShardings matching ``params``' structure.

    ``zero_data_shard=True`` is the ZeRO-1 layout for OPTIMIZER state:
    leaves with no tensor-parallel rule are sharded along dim 0 over
    the data axis (when divisible) instead of replicated. The jitted
    step's in/out shardings then make XLA keep the momentum buffers
    partitioned — each data rank stores and updates 1/data of them, and
    the parameter update is all-gathered where applied. Params
    themselves stay replicated (DS2-scale models fit; this trades one
    gather for (data-1)/data of the adamw mu/nu memory)."""

    def keyname(k):
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                return str(getattr(k, attr))
        return str(k)

    def one(path_tuple, leaf):
        path = "/".join(keyname(k) for k in path_tuple)
        spec = param_spec(path)
        shape = getattr(leaf, "shape", ())
        if (zero_data_shard and spec == P() and len(shape)
                and shape[0] % mesh.shape[DATA_AXIS] == 0
                and shape[0] >= mesh.shape[DATA_AXIS]):
            spec = P(DATA_AXIS)
        # A dim that doesn't divide by its mesh axis (e.g. the 29-way EN
        # head over model=2) falls back to replication; the big vocab
        # heads this rule exists for (AISHELL ~4.3k) divide cleanly. A
        # spec naming an axis the mesh doesn't have (pipe-stacked params
        # on a 2-axis mesh, e.g. single-device infer) also replicates.
        for dim, axis in enumerate(spec):
            if axis is None:
                continue
            if axis not in mesh.shape:
                return NamedSharding(mesh, P())
            if dim >= len(shape) or shape[dim] % mesh.shape[axis] != 0:
                logger.warning(
                    "tensor-parallel spec %s for %r dropped: dim %d of "
                    "shape %s not divisible by mesh axis %r (size %d); "
                    "replicating", spec, path, dim, tuple(shape), axis,
                    mesh.shape[axis])
                return NamedSharding(mesh, P())
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params)


def shard_batchwise(fn, mesh: Optional[Mesh], n_sharded: int):
    """Make a batch-elementwise op partition over the ``data`` axis.

    Pallas kernels are opaque custom calls to the XLA SPMD partitioner:
    left inside a GSPMD-jitted step on a multi-device mesh they cannot
    be auto-partitioned, so the batch would be all-gathered and the
    kernel run replicated (losing data parallelism) or fail to lower.
    The TPU-native composition is ``jax.shard_map``: each device runs
    the kernel on its local batch shard. The map is manual over ALL
    mesh axes (partial-manual ``axis_names={DATA_AXIS}`` only works
    under an enclosing jit, but ``model.init`` applies the model
    eagerly); kernel operands are replicated along ``model`` (specs
    don't mention it), so tensor-parallel layers around the kernel are
    unaffected — GSPMD reshards at the shard_map boundary as needed.

    The first ``n_sharded`` positional args are split on their leading
    (batch) dim; the rest (weights/scalars) are replicated. All outputs
    are batch-leading. No-op for single-device data axes — the
    single-chip hot path (``ds2_full.train_1chip``) stays
    byte-identical.
    """
    if mesh is None or mesh.shape[DATA_AXIS] == 1:
        return fn

    def wrapper(*args):
        in_specs = tuple(P(DATA_AXIS) if i < n_sharded else P()
                         for i in range(len(args)))
        # check_vma=False: pallas_call out_shapes carry no varying-
        # mesh-axes metadata, which the vma validity checks require;
        # outputs are genuinely equal along the unmentioned model axis
        # (replicated operands, deterministic kernel).
        return shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=P(DATA_AXIS),
            check_vma=False)(*args)

    return wrapper


def process_local_span(global_batch: int) -> Tuple[int, int]:
    """[lo, hi) rows of a global batch this process is responsible for,
    by the process-major equal split. The host data pipeline loads only
    these rows; Trainer cross-checks this arithmetic against the actual
    sharding via ``process_local_rows`` once at startup."""
    p, n = jax.process_index(), jax.process_count()
    return global_batch * p // n, global_batch * (p + 1) // n


@functools.lru_cache(maxsize=64)
def process_local_rows(mesh: Mesh, global_batch: int) -> Tuple[int, int]:
    """[lo, hi) rows of the global batch owned by this process.

    Row ownership under ``batch_sharding`` follows the mesh's device
    order; ``jax.devices()`` is process-major, so each process owns one
    contiguous block. Verified against the sharding's own index map
    rather than assumed. Cached — this sits on the per-step input path
    and depends only on (mesh, global_batch).
    """
    sh = batch_sharding(mesh)
    idx_map = sh.addressable_devices_indices_map((global_batch,))
    # set(): devices differing only in their model coordinate replicate
    # the same batch rows (P("data") ignores the model axis) and must
    # count once.
    starts = sorted({(s[0].start or 0, s[0].stop if s[0].stop is not None
                      else global_batch) for s in idx_map.values()})
    lo, hi = starts[0][0], starts[-1][1]
    # Contiguity check: the distinct per-device slices must tile [lo, hi).
    expect = lo
    for s, e in starts:
        if s != expect:
            raise ValueError(
                f"non-contiguous local batch rows {starts}; custom device "
                "orders are not supported by the host data pipeline")
        expect = e
    return lo, hi


def shard_batch(mesh: Mesh, batch, time_sharded: bool = False):
    """Device-put a host batch with the data-parallel sharding.

    Single-process: a plain sharded device_put. Multi-process (after
    ``jax.distributed.initialize``): every process passes arrays of the
    GLOBAL batch shape but only its own rows (``process_local_rows``)
    need real data — the global jax.Array is assembled from each
    process's addressable shards, which is how the reference's
    per-rank data loading maps onto jax (SURVEY.md §3.5).

    ``time_sharded`` is the sequence-parallel layout
    (train.sequence_parallel): features shard along TIME over the data
    axis, everything else replicates — batch rows are not a parallel
    dimension there.
    """
    if time_sharded:
        if jax.process_count() > 1:
            raise NotImplementedError(
                "sequence-parallel training is single-process")

        def put_sp(k, x):
            spec = P(None, DATA_AXIS) if k == "features" else P()
            return jax.device_put(x, NamedSharding(mesh, spec))

        return {k: put_sp(k, v) for k, v in batch.items()}
    sh = batch_sharding(mesh)
    if jax.process_count() == 1:
        return jax.tree.map(lambda x: jax.device_put(x, sh), batch)

    # One row-span lookup per batch (all leaves share the leading dim),
    # not one per leaf — this sits on the per-step input path.
    b = len(next(iter(batch.values())))
    lo, hi = process_local_rows(mesh, b)

    def put(x):
        x = np.asarray(x)
        return jax.make_array_from_process_local_data(sh, x[lo:hi], x.shape)

    return jax.tree.map(put, batch)
