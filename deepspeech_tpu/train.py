"""Training: jit-compiled step over a (data, model) mesh + epoch loop.

The reference's L5 trainer (SURVEY.md §3.1) maps to:
- one jitted ``train_step`` = forward (conv+RNN+head) + CTC + backward +
  gradient all-reduce + optimizer update. The all-reduce is implicit:
  batches are sharded over the ``data`` mesh axis, params are
  replicated, so XLA inserts the psum during backprop and schedules it
  to overlap with the rest of the backward pass — this *is* the NCCL
  replacement, with zero backend code.
- SortaGrad epoch switch and bucketed static shapes come from the data
  layer; each (bucket_frames,) shape compiles once.
- DS2-era hyperparameters: SGD+momentum, global-norm clipping, warmup
  then per-epoch 1/anneal^epoch decay.

CLI: ``python -m deepspeech_tpu.train --config=dev_slice [--synthetic=N]
[--section.key=value ...]``
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax

from . import obs
from .config import Config
from .data import CharTokenizer, DataPipeline
from .decode.greedy import greedy_decode, ids_to_texts
from jax.sharding import NamedSharding, PartitionSpec as P

from .models import create_model
from .ops import ctc_loss_mean
from .parallel import (DATA_AXIS, batch_sharding, make_mesh,
                       param_shardings, replicated, shard_batch)
from .resilience import faults
from .resilience.guardian import STEP_HIST
from .utils.logging import JsonlLogger, Throughput


class _LoggedStep(NamedTuple):
    """A step ``Trainer.fit`` has handed over and owes a log line."""
    step: int        # steps done, as the line says
    epoch: int
    metrics: Dict    # the step's outputs, still on the device
    dropped: list    # lm: every step's dropped counter since the
                     # last line, this step's included


@flax.struct.dataclass
class TrainState:
    step: jnp.ndarray
    params: Any
    batch_stats: Any
    opt_state: Any


def make_lr_schedule(cfg: Config, steps_per_epoch: int
                     ) -> Callable[[jnp.ndarray], jnp.ndarray]:
    t = cfg.train

    def schedule(step):
        warm = jnp.minimum(
            (step + 1) / max(t.warmup_steps, 1), 1.0)
        epoch = step // max(steps_per_epoch, 1)
        anneal = jnp.power(t.lr_anneal, epoch.astype(jnp.float32))
        return t.learning_rate * warm / anneal

    return schedule


def host_lr(cfg: Config, steps_per_epoch: int, step: int) -> float:
    """``make_lr_schedule``'s formula in Python floats, for the log
    line: the loop's turn after a logged step issues no device
    computation (``tests/test_train_ahead.py`` holds the two faces
    together)."""
    t = cfg.train
    warm = min((step + 1) / max(t.warmup_steps, 1), 1.0)
    epoch = step // max(steps_per_epoch, 1)
    return t.learning_rate * warm / t.lr_anneal ** epoch


def make_optimizer(cfg: Config, steps_per_epoch: int
                   ) -> optax.GradientTransformation:
    """Optimizer with the learning rate as an *injected hyperparam*
    (``optax.inject_hyperparams``) instead of a baked-in schedule: the
    train step writes ``opt_state.hyperparams["learning_rate"] =
    schedule(step) * lr_scale`` each step, so the guardian's LR
    backoff flows through optax itself — the optimizer's own
    bookkeeping (momentum trace, recorded lr) sees the backed-off
    step, rather than a post-hoc host-side rescale of the emitted
    update that optax never knew about."""
    t = cfg.train
    if t.optimizer not in ("sgd", "adamw"):
        raise ValueError(f"unknown optimizer {t.optimizer!r}")
    schedule = make_lr_schedule(cfg, steps_per_epoch)

    def base(learning_rate):
        if t.optimizer == "sgd":
            opt = optax.sgd(learning_rate, momentum=t.momentum,
                            nesterov=True)
        else:
            opt = optax.adamw(learning_rate,
                              weight_decay=t.weight_decay)
        return optax.chain(
            optax.clip_by_global_norm(t.grad_clip_norm), opt)

    return optax.inject_hyperparams(base)(
        learning_rate=float(schedule(jnp.zeros((), jnp.int32))))


def select_loss_fn(cfg: Config, mesh=None):
    from .utils.impl import resolve_impl

    impl = resolve_impl(cfg.train.loss_impl, oracle="jnp")
    if impl == "pallas":
        from .utils.impl import interpret_default
        from .ops.ctc_pallas import ctc_loss_pallas
        from .parallel.mesh import shard_batchwise

        interpret = interpret_default()
        # Multi-device meshes partition the kernel over the data axis
        # via shard_map (the kernel is batch-elementwise; the mean over
        # the sharded per-utterance losses stays in GSPMD auto mode).
        per_utt = shard_batchwise(
            lambda lg, lb, ln, ll: ctc_loss_pallas(lg, lb, ln, ll,
                                                   interpret),
            mesh, n_sharded=4)

        def mean_loss(logits, labels, lens, label_lens):
            return jnp.mean(per_utt(logits, labels, lens, label_lens))
    else:
        mean_loss = ctc_loss_mean

    def ctc_loss(logits, labels, lens, label_lens):
        # The scope the device's time is read under (obs/layers.py).
        with jax.named_scope("ctc_loss"):
            return mean_loss(logits, labels, lens, label_lens)

    return ctc_loss


def create_train_state(cfg: Config, rng: jax.Array, sample_batch: Dict,
                       optimizer: optax.GradientTransformation,
                       mesh=None) -> Tuple[Any, TrainState]:
    def few(frames: int):
        """A few rows and frames of the sample: no parameter's shape
        depends on the batch, so the rnnt and lm objectives initialise
        through their training path as ONE compiled program on these
        (the eager path compiles every primitive on its own)."""
        return (jnp.asarray(sample_batch["features"][:8, :frames]),
                jnp.minimum(jnp.asarray(sample_batch["feat_lens"][:8]),
                            frames),
                jnp.asarray(sample_batch["labels"][:8]),
                jnp.asarray(sample_batch["label_lens"][:8]))

    if cfg.train.objective == "rnnt":
        from .models.transducer import create_rnnt_model

        model = create_rnnt_model(cfg.model, mesh=mesh)
        variables = jax.jit(partial(
            model.init, train=False, method=type(model).loss))(
            rng, *few(8 * cfg.model.time_stride))
    elif cfg.train.objective == "lm":
        from .models.lfm2 import create_lfm2_model

        model = create_lfm2_model(cfg.model, cfg.data.max_label_len)
        # The least positions that hold the few frames, on the oracle.
        small = create_lfm2_model(
            dataclasses.replace(cfg.model, lfm_seq_positions=0,
                                moe_impl="xla"), cfg.data.max_label_len)
        variables = jax.jit(partial(small.init, method="loss"))(
            rng, *few(2 * cfg.model.frame_stack))
    else:
        model = create_model(cfg.model, mesh=mesh)
        variables = model.init(
            rng, jnp.asarray(sample_batch["features"]),
            jnp.asarray(sample_batch["feat_lens"]), train=False)
    params = variables["params"]
    # What is carried and never trained: batch-norm statistics, or the
    # lm objective's buffers (the experts' selection bias), which no
    # optimizer sees.
    batch_stats = variables.get(_stats_collection(cfg), {})
    opt_state = optimizer.init(params)
    return model, TrainState(step=jnp.zeros((), jnp.int32), params=params,
                             batch_stats=batch_stats, opt_state=opt_state)


def _stats_collection(cfg: Config) -> str:
    return "buffers" if cfg.train.objective == "lm" else "batch_stats"


def state_shardings(mesh, state: TrainState,
                    zero_opt: bool = False) -> TrainState:
    """Sharding tree for TrainState.

    ``param_shardings`` keys off path suffixes (e.g. ``head/kernel``),
    and optimizer-state trees (sgd trace / adamw mu,nu) embed the same
    param paths, so the tensor-parallel specs propagate to the matching
    momentum buffers automatically; everything else is replicated —
    except with ``zero_opt`` (TrainConfig.zero_opt_sharding), which
    additionally ZeRO-1-shards the non-TP optimizer leaves over the
    data axis (see param_shardings).
    """
    return TrainState(
        step=replicated(mesh),
        params=param_shardings(mesh, state.params),
        batch_stats=param_shardings(mesh, state.batch_stats),
        opt_state=param_shardings(mesh, state.opt_state,
                                  zero_data_shard=zero_opt),
    )


def make_train_step(cfg: Config, model, optimizer, mesh, state_sh,
                    guardian: bool = False, lr_schedule=None):
    """Build the jitted step. With ``guardian`` the step takes a third
    ``ctl={"lr_scale"}`` argument, additionally reports the update-norm,
    and *gates the state transition on device*: a step whose loss /
    grad-norm / update-norm is non-finite returns the previous state
    bit-exactly (``jnp.where`` over every leaf — required because the
    donated input state is consumed, so the host cannot "just keep" it).

    ``lr_schedule`` is the step -> lr function written into the
    optimizer's injected ``learning_rate`` hyperparam every step (the
    guardian's ``lr_scale`` multiplies it INSIDE the optimizer —
    see :func:`make_optimizer`); defaults to the cfg schedule with
    ``steps_per_epoch=1`` for callers that never fit epochs (AOT
    compile probes).
    """
    loss_fn = (None if cfg.train.objective in ("rnnt", "lm")
               else select_loss_fn(cfg, mesh=mesh))
    schedule = (lr_schedule if lr_schedule is not None
                else make_lr_schedule(cfg, 1))

    def opt_state_at(state: TrainState, lr_scale=None):
        """The input opt_state with this step's learning rate written
        into the injected hyperparam — schedule(step), times the
        guardian's backoff when given."""
        lr = schedule(state.step)
        if lr_scale is not None:
            lr = lr * lr_scale
        opt = state.opt_state
        return opt._replace(
            hyperparams={**opt.hyperparams, "learning_rate": lr})

    accum = max(cfg.train.accum_steps, 1)

    if cfg.train.sequence_parallel:
        from .models.layers import BN_MOMENTUM
        from .parallel.seqpar import sp_loss

        def grads_of(params, stats, mb):
            def loss_of(p):
                loss, batch_stats = sp_loss(
                    cfg.model, {"params": p, "batch_stats": stats},
                    mb["features"], mb["feat_lens"], mb["labels"],
                    mb["label_lens"], mesh)
                # Running-average update mirrors MaskedBatchNorm.
                new_stats = jax.tree.map(
                    lambda old, b: BN_MOMENTUM * old
                    + (1 - BN_MOMENTUM) * b, stats, batch_stats)
                return loss, new_stats

            return jax.value_and_grad(loss_of, has_aux=True)(params)
    elif cfg.train.objective == "rnnt":
        def grads_of(params, stats, mb):
            def loss_of(p):
                # The tiled joint + loss (ops/transducer.py): no
                # [B, T', U+1, V] lattice at any size.
                (per_utt, lens), mutated = model.apply(
                    {"params": p, "batch_stats": stats},
                    mb["features"], mb["feat_lens"], mb["labels"],
                    mb["label_lens"], True, mutable=["batch_stats"],
                    method=type(model).loss)
                # Zero-frame rows carry the loss's -LOG_ZERO sentinel
                # (no lattice, no likelihood) — average over real rows
                # only so one empty/corrupt utterance can't blow up the
                # reported loss or the gradient scale.
                valid = (lens > 0).astype(per_utt.dtype)
                loss = jnp.sum(per_utt * valid) \
                    / jnp.maximum(jnp.sum(valid), 1.0)
                # The lstmp encoder has layer norm, no batch norm.
                return loss, mutated.get("batch_stats", stats)

            return jax.value_and_grad(loss_of, has_aux=True)(params)
    elif cfg.train.objective == "lm":
        def grads_of(params, stats, mb):
            def loss_of(p):
                # Summed cross-entropy of each transcript, mean over
                # utterances; the routing counters ride with the loss
                # (same fetch, no second sync).
                per_utt, routing = model.apply(
                    {"params": p, "buffers": stats},
                    mb["features"], mb["feat_lens"], mb["labels"],
                    mb["label_lens"], method="loss")
                return jnp.mean(per_utt), (stats, routing)

            return jax.value_and_grad(loss_of, has_aux=True)(params)
    else:
        def grads_of(params, stats, mb):
            def loss_of(p):
                (logits, lens), mutated = model.apply(
                    {"params": p, "batch_stats": stats},
                    mb["features"], mb["feat_lens"], train=True,
                    mutable=["batch_stats"])
                loss = loss_fn(logits, mb["labels"], lens,
                               mb["label_lens"])
                return loss, mutated["batch_stats"]

            return jax.value_and_grad(loss_of, has_aux=True)(params)

    def forward(state: TrainState, batch: Dict):
        if accum == 1:
            (loss, new_stats), grads = grads_of(
                state.params, state.batch_stats, batch)
        else:
            # Microbatch scan: grads averaged, BN stats threaded through
            # sequentially (each microbatch sees the previous running
            # stats, like accum separate small steps would). The split
            # is STRIDED (row r -> microbatch r % accum): each device's
            # contiguous row block contributes rows to every microbatch
            # from its own shard, so the reshape needs no cross-device
            # movement (a contiguous split would all-to-all the batch
            # over the data axis every step).
            mbs = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x.reshape((x.shape[0] // accum, accum)
                              + x.shape[1:]).swapaxes(0, 1),
                    NamedSharding(mesh, P(None, DATA_AXIS))),
                batch)

            def body(carry, mb):
                stats, gacc, lacc = carry
                (mloss, stats), g = grads_of(state.params, stats, mb)
                return (stats, jax.tree.map(jnp.add, gacc, g),
                        lacc + mloss), None

            zeros = jax.tree.map(jnp.zeros_like, state.params)
            (new_stats, gsum, lsum), _ = jax.lax.scan(
                body, (state.batch_stats, zeros, jnp.float32(0)), mbs)
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss = lsum / accum
        return loss, new_stats, grads

    def split_aux(aux):
        """What a step carries besides its loss: the new statistics
        and, for the lm objective, the routing counters that ride with
        the loss to the host."""
        if cfg.train.objective == "lm":
            return aux[0], {"routing": aux[1]}
        return aux, {}

    def step_fn(state: TrainState, batch: Dict) -> Tuple[TrainState, Dict]:
        loss, new_stats, grads = forward(state, batch)
        new_stats, routing = split_aux(new_stats)
        with jax.named_scope("grad_norm"):
            grad_norm = optax.global_norm(grads)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(
                grads, opt_state_at(state), state.params)
            new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(step=state.step + 1, params=new_params,
                               batch_stats=new_stats, opt_state=new_opt)
        metrics = {"loss": loss, "grad_norm": grad_norm, **routing}
        return new_state, metrics

    def guarded_step_fn(state: TrainState, batch: Dict,
                        ctl: Dict) -> Tuple[TrainState, Dict]:
        loss, new_stats, grads = forward(state, batch)
        new_stats, routing = split_aux(new_stats)
        with jax.named_scope("grad_norm"):
            grad_norm = optax.global_norm(grads)
        with jax.named_scope("optimizer"):
            # The backoff multiplies the schedule INSIDE the optimizer
            # (injected learning_rate hyperparam), so momentum
            # bookkeeping and the recorded lr both see the backed-off
            # step.
            updates, new_opt = optimizer.update(
                grads, opt_state_at(state, ctl["lr_scale"]), state.params)
            # Health is judged on the RAW update norm (what an unscaled
            # step would have applied) so the soft-anomaly statistics
            # don't shift with the backoff level; lr enters the emitted
            # update linearly, so dividing the scale back out is exact.
            update_norm = optax.global_norm(updates) / ctl["lr_scale"]
            new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(step=state.step + 1, params=new_params,
                               batch_stats=new_stats, opt_state=new_opt)
        ok = (jnp.isfinite(loss) & jnp.isfinite(grad_norm)
              & jnp.isfinite(update_norm))
        # A bad step must be a bit-exact no-op: every leaf (params, BN
        # stats, optimizer state, step counter) falls back to its
        # previous value on device — the donated input cannot be kept
        # host-side, and the rollback bit-identity scenario depends on
        # skipped batches leaving literally no trace in the state.
        new_state = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                 new_state, state)
        metrics = {"loss": loss, "grad_norm": grad_norm,
                   "update_norm": update_norm, "applied": ok, **routing}
        return new_state, metrics

    if cfg.train.sequence_parallel:
        # Time (dim 1 of features) is the parallel dimension; batch
        # rows replicate (parallel/seqpar.py layout).
        batch_sh = {"features": NamedSharding(mesh, P(None, DATA_AXIS)),
                    "feat_lens": replicated(mesh),
                    "labels": replicated(mesh),
                    "label_lens": replicated(mesh)}
    else:
        data_sh = batch_sharding(mesh)
        batch_sh = jax.tree.map(lambda _: data_sh, _batch_template())
    if guardian:
        return jax.jit(
            guarded_step_fn,
            in_shardings=(state_sh, batch_sh,
                          {"lr_scale": replicated(mesh)}),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
    return jax.jit(
        step_fn,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    )


def _batch_template():
    return {"features": 0, "feat_lens": 0, "labels": 0, "label_lens": 0}


def _addressable_rows(arr) -> np.ndarray:
    """This process's rows of a batch-sharded global array, assembled
    from its addressable shards in batch order (devices differing only
    in their model coordinate hold identical rows — dedupe by start)."""
    shards = {}
    for s in arr.addressable_shards:
        shards[s.index[0].start or 0] = np.asarray(s.data)
    return np.concatenate([shards[k] for k in sorted(shards)], axis=0)


def _score_utt(counts: np.ndarray, ref: str, hyp: str) -> None:
    """Accumulate (werr, wtot, cerr, ctot, n) — ONE layout shared by
    both eval branches."""
    from .metrics import char_errors, word_errors

    we, wn = word_errors(ref, hyp)
    ce, cn = char_errors(ref, hyp)
    counts += (we, wn, ce, cn, 1)


def _counts_summary(counts: np.ndarray) -> Dict[str, float]:
    return {"wer": counts[0] / max(counts[1], 1),
            "cer": counts[2] / max(counts[3], 1),
            "n_utts": int(counts[4])}


def make_eval_step(model):
    @jax.jit
    def eval_fn(params, batch_stats, batch):
        logits, lens = model.apply(
            {"params": params, "batch_stats": batch_stats},
            batch["features"], batch["feat_lens"], train=False)
        ids, out_lens = greedy_decode(logits, lens)
        return ids, out_lens

    return eval_fn


class Trainer:
    """Epoch loop: SortaGrad data, jitted step, periodic eval/ckpt."""

    def __init__(self, cfg: Config, pipeline: DataPipeline,
                 tokenizer: CharTokenizer,
                 eval_pipeline: Optional[DataPipeline] = None,
                 logger: Optional[JsonlLogger] = None,
                 mesh=None, preempt=None):
        self.cfg = cfg
        self.pipeline = pipeline
        self.eval_pipeline = eval_pipeline
        self.tokenizer = tokenizer
        self.logger = logger or JsonlLogger()
        # Optional resilience.PreemptionGuard: fit polls it each step
        # and converts SIGTERM into an emergency checkpoint + clean
        # return instead of a killed process mid-save.
        self.preempt = preempt
        self.mesh = mesh if mesh is not None else make_mesh(
            cfg.train.mesh_shape)
        if jax.process_count() > 1:
            # The host pipeline fills only this process's batch rows by
            # the equal process-major split; verify once that the mesh's
            # actual row ownership agrees (parallel/mesh.py).
            from .parallel.mesh import process_local_rows, process_local_span

            b = cfg.data.batch_size
            local = process_local_rows(self.mesh, b)
            # A batch axis that does NOT cross processes (e.g. a pipe
            # axis spans them instead: data=1 layouts) replicates every
            # row on every process — legitimate only when the pipeline
            # really materializes the full global batch everywhere
            # (synthetic pipelines do; the manifest pipeline loads only
            # its process-major span and must keep the strict check).
            replicated_ok = (local == (0, b) and getattr(
                self.pipeline, "provides_global_batches", False))
            if local != process_local_span(b) and not replicated_ok:
                raise ValueError(
                    "mesh device order breaks the process-major batch "
                    "split assumed by the data pipeline: "
                    f"{local} != {process_local_span(b)}")
        accum = max(cfg.train.accum_steps, 1)
        data_size = int(self.mesh.shape[DATA_AXIS])
        if cfg.train.sequence_parallel:
            # Time replaces batch as the parallel dimension; batch rows
            # replicate, so no row-divisibility constraint — instead
            # every bucket's frame count must split evenly over shards.
            from .parallel.seqpar import sp_frame_multiple

            if accum > 1 or cfg.model.pipeline_stages > 1:
                raise ValueError("sequence_parallel excludes "
                                 "accum_steps>1 and pipeline_stages>1")
            if "pallas" in (cfg.model.rnn_impl, cfg.train.loss_impl):
                raise ValueError(
                    "sequence_parallel runs the XLA scan cells and the "
                    "alpha-relay CTC; explicit pallas impls are not "
                    "supported (use 'auto' or 'xla'/'jnp')")
            if jax.process_count() > 1:
                raise ValueError("sequence_parallel is single-process")
            mult = sp_frame_multiple(cfg.model, data_size)
            bad = [f for f in cfg.data.bucket_frames if f % mult]
            if bad:
                raise ValueError(
                    f"bucket_frames {bad} must divide by "
                    f"shards*time_stride = {mult}")
        elif cfg.data.batch_size % (accum * data_size):
            raise ValueError(
                f"batch_size {cfg.data.batch_size} must divide by "
                f"accum_steps*data = {accum}*{data_size}")
        objective = cfg.train.objective
        if objective not in ("ctc", "rnnt", "lm"):
            # A typo must not silently train the CTC stack.
            raise ValueError(
                f"train.objective={objective!r}; 'ctc', 'rnnt' or 'lm'")
        if objective in ("rnnt", "lm"):
            if cfg.train.sequence_parallel or cfg.model.pipeline_stages > 1:
                raise ValueError(
                    f"objective={objective!r} excludes "
                    "sequence_parallel and pipeline_stages>1")
            if jax.process_count() > 1:
                # Fail at construction, not after an epoch of work in
                # the (host-loop) transducer eval.
                raise ValueError(f"objective={objective!r} is "
                                 "single-process")
        if objective == "lm" and accum > 1:
            raise ValueError("objective='lm' excludes accum_steps>1 "
                             "(its step carries the routing counters)")
        if objective == "lm":
            from .models.lfm2 import HYBRID, LINEAR
            from .ops import ssd_pallas

            m = cfg.model
            if (HYBRID in m.lfm_layer_types and ssd_pallas.in_kernels(
                    m.ssm_d_ssm // m.ssm_heads, m.ssm_state)) or (
                    LINEAR in m.lfm_layer_types and ssd_pallas.in_kernels(
                        m.lin_head_dim, m.lin_head_dim)):
                # Fail at construction, not in the first step's trace.
                raise NotImplementedError(
                    "objective='lm': the state-space mixer's sequence "
                    "form runs here as the kernel ssd_chunk_scan, which "
                    "has no backward pass yet; this preset is served "
                    "(decode.mode='lm_greedy'), not trained")
        if objective == "lm" and eval_pipeline is not None:
            from .models.lfm2 import uncached_kinds

            if uncached_kinds(cfg.model):
                # Fail at construction, not after an epoch of work.
                raise ValueError(
                    "objective='lm': transcripts are decoded through a "
                    "cache, which latent attention, grouped-query "
                    "attention and the hybrid of a state-space mixer "
                    "beside attention have; this preset's layers lack a "
                    "convolution state, so no in-training eval: pass no "
                    "eval_pipeline")
        stages = cfg.model.pipeline_stages
        if stages > 1:
            # Training with a pipelined model silently falling back to
            # the sequential path would replicate every stage's weights;
            # require the mesh to actually carry the pipe axis.
            if ("pipe" not in self.mesh.axis_names
                    or self.mesh.shape["pipe"] != stages):
                raise ValueError(
                    f"pipeline_stages={stages} needs mesh_shape=(data, "
                    f"{stages}, model); mesh has "
                    f"{dict(self.mesh.shape)}")
            micro = cfg.model.pipeline_microbatches or stages
            if cfg.data.batch_size % (accum * micro * data_size):
                raise ValueError(
                    f"batch_size {cfg.data.batch_size} must divide by "
                    f"accum*microbatches*data = "
                    f"{accum}*{micro}*{data_size}")
            # The pipelined middle layers run the XLA scan cell (the
            # Pallas cells' shard_map composition doesn't nest inside
            # the pipe schedule yet). An explicit pallas request must
            # fail loudly — never quietly train the other impl
            # (utils/impl.py contract); 'auto' resolves with a note.
            if cfg.model.rnn_impl == "pallas":
                raise ValueError(
                    "rnn_impl='pallas' is not supported with "
                    "pipeline_stages>1 (layers 1+ run the XLA scan); "
                    "use rnn_impl='xla' or 'auto'")
            from .utils.impl import resolve_impl
            if resolve_impl(cfg.model.rnn_impl, oracle="xla") == "pallas":
                self.logger.log(
                    "pipeline_note",
                    note="pipeline_stages>1: layer 0 uses the fused "
                         "Pallas cell, pipelined layers 1+ use the XLA "
                         "scan cell")
        self.steps_per_epoch = max(pipeline.batches_per_epoch(1), 1)
        self.optimizer = make_optimizer(cfg, self.steps_per_epoch)
        self.lr_schedule = make_lr_schedule(cfg, self.steps_per_epoch)
        self.tb = None
        if cfg.train.tensorboard_dir:
            from .utils.logging import TensorBoardLogger

            self.tb = TensorBoardLogger(cfg.train.tensorboard_dir)
        rng = jax.random.PRNGKey(cfg.train.seed)
        sample = (pipeline.peek() if hasattr(pipeline, "peek")
                  else next(iter(pipeline.epoch(0))))
        self.model, self.state = create_train_state(
            cfg, rng, sample, self.optimizer, mesh=self.mesh)
        self.state_sh = state_shardings(
            self.mesh, self.state,
            zero_opt=cfg.train.zero_opt_sharding)
        self.state = jax.device_put(self.state, self.state_sh)
        # Self-healing ladder (resilience/guardian.py): DS2_GUARDIAN
        # enables + configures; cfg.train.guardian enables with the
        # defaults when the env is silent.
        from .resilience.guardian import GuardianConfig

        self.guardian_cfg = GuardianConfig.from_env()
        if self.guardian_cfg is None and cfg.train.guardian:
            self.guardian_cfg = GuardianConfig()
        self.train_step = make_train_step(
            cfg, self.model, self.optimizer, self.mesh, self.state_sh,
            guardian=self.guardian_cfg is not None,
            lr_schedule=self.lr_schedule)
        self.eval_step = (None if cfg.train.objective in ("rnnt", "lm")
                          else make_eval_step(self.model))
        self.ckpt = None
        if cfg.train.checkpoint_dir:
            from .checkpoint import CheckpointManager

            self.ckpt = CheckpointManager(
                cfg.train.checkpoint_dir,
                keep=cfg.train.keep_checkpoints,
                last_good_keep=(self.guardian_cfg.ring_size
                                if self.guardian_cfg else 2))
        # lm objective: each step's count of dropped pairs, as device
        # scalars, since the last sync (fit reads them there).
        self._dropped = []
        self.guardian = None
        if self.guardian_cfg is not None:
            from .resilience.guardian import TrainingGuardian

            self.guardian = TrainingGuardian(self.guardian_cfg,
                                             ckpt=self.ckpt)
        self.start_epoch = 0

    def maybe_restore(self) -> None:
        if self.ckpt is None:
            return
        restored = self.ckpt.restore(template={
            "state": self.state, "epoch": 0})
        if restored is not None:
            self.state = restored["state"]
            self.start_epoch = int(restored["epoch"])
            self.logger.log("restore", step=int(self.state.step),
                            epoch=self.start_epoch)

    def _check_dropless(self) -> None:
        """Raise if a step since the last sync dropped routed pairs
        (lm objective): called where the loop syncs anyway."""
        pending, self._dropped = self._dropped, []
        if pending:
            obs.check_dropless(pending)

    def save(self, epoch: int) -> None:
        # No checkpoint holds parameters that a dropping step made.
        self._check_dropless()
        if self.ckpt is not None:
            with obs.span("train.checkpoint", step=int(self.state.step)):
                self.ckpt.save(int(self.state.step),
                               {"state": self.state, "epoch": epoch})

    def evaluate(self) -> Dict[str, float]:
        if self.cfg.train.objective == "rnnt":
            return self._evaluate_rnnt()
        if self.cfg.train.objective == "lm":
            return self._evaluate_lm()
        if self.cfg.decode.mode != "greedy":
            # Beam search + LM rescoring live in infer.py (decode/beam.py);
            # in-training eval always uses the cheap greedy path.
            self.logger.log("eval_note",
                            note="in-training eval uses greedy decode; run "
                                 "deepspeech_tpu.infer for beam+LM")
        pipe = self.eval_pipeline or self.pipeline
        multi = jax.process_count() > 1
        from .parallel.mesh import process_local_rows

        # Each process scores only the batch rows it owns (the host
        # batch has real label rows only for this process's span, and
        # the matching device output rows are already addressable here —
        # no per-batch collective); the error counts are summed across
        # ranks once at the end. Single-process is the lo=0, hi=b case.
        counts = np.zeros((5,), np.int64)  # werr, wtot, cerr, ctot, n
        for batch, n_valid in pipe.eval_epoch():
            # Under sequence-parallel training the batch rows don't
            # shard over the data axis (time does); eval places
            # features time-sharded and lets GSPMD run the offline
            # graph with whatever layout it derives.
            sharded = shard_batch(
                self.mesh, batch,
                time_sharded=self.cfg.train.sequence_parallel)
            ids, out_lens = self.eval_step(self.state.params,
                                           self.state.batch_stats, sharded)
            b = len(batch["feat_lens"])
            if multi:
                lo, hi = process_local_rows(self.mesh, b)
                if (lo, hi) == (0, b) and jax.process_index() != 0:
                    # Replicated batch axis (e.g. a pure-PP mesh with
                    # data=1): every rank owns every row; only rank 0
                    # scores, or the allgather would double-count.
                    lo = hi = 0
                ids_np = _addressable_rows(ids)
                lens_np = _addressable_rows(out_lens)
            else:
                lo, hi = 0, b
                ids_np, lens_np = np.asarray(ids), np.asarray(out_lens)
            hyps = ids_to_texts(ids_np, lens_np, self.tokenizer)
            for j, g in enumerate(range(lo, min(hi, n_valid))):
                ref = self.tokenizer.decode(
                    batch["labels"][g][:batch["label_lens"][g]])
                _score_utt(counts, ref, hyps[j])
        if multi:
            from jax.experimental import multihost_utils

            counts = np.sum(multihost_utils.process_allgather(counts),
                            axis=0)
        return _counts_summary(counts)

    def _evaluate_rnnt(self) -> Dict[str, float]:
        """Greedy transducer eval (host time-synchronous loop —
        models/transducer.rnnt_greedy_decode). Single-process."""
        if jax.process_count() > 1:
            raise ValueError("objective='rnnt' eval is single-process")
        from .models.transducer import rnnt_greedy_decode

        pipe = self.eval_pipeline or self.pipeline
        variables = {"params": self.state.params,
                     "batch_stats": self.state.batch_stats}
        counts = np.zeros((5,), np.int64)
        for batch, n_valid in pipe.eval_epoch():
            hyp_ids = rnnt_greedy_decode(
                self.model, variables, jnp.asarray(batch["features"]),
                jnp.asarray(batch["feat_lens"]),
                max_label_len=self.cfg.data.max_label_len)
            for g in range(n_valid):
                ref = self.tokenizer.decode(
                    batch["labels"][g][:batch["label_lens"][g]])
                _score_utt(counts, ref, self.tokenizer.decode(hyp_ids[g]))
        return _counts_summary(counts)

    def _evaluate_lm(self) -> Dict[str, float]:
        """WER/CER of greedy transcripts through the cache
        (decode/lm_greedy.py; it raises, naming what is missing, for a
        preset whose layers have no decode form). Single-process."""
        from .decode.lm_greedy import LMGreedy

        pipe = self.eval_pipeline or self.pipeline
        engine = LMGreedy(self.cfg, self.state.params,
                          self.state.batch_stats)
        counts = np.zeros((5,), np.int64)
        for batch, n_valid in pipe.eval_epoch():
            out = engine.transcribe(batch["features"], batch["feat_lens"])
            for g in range(n_valid):
                ref = self.tokenizer.decode(
                    batch["labels"][g][:batch["label_lens"][g]])
                _score_utt(counts, ref, self.tokenizer.decode(
                    out["ids"][g][:out["tokens"][g]]))
        return _counts_summary(counts)

    def _log_step(self, logged: _LoggedStep, rate: float,
                  ahead: bool) -> Dict[str, float]:
        """The host's turn after a logged step, one child span a thing
        it does (obs/trace.py): block on THAT step's loss, so that its
        ``train_step`` event is a completed step, then write its line.
        Only transfers of the step's outputs: with ``ahead`` the next
        step is already queued, and a device computation issued here
        would complete only when that step does."""
        step, metrics = logged.step, logged.metrics
        reg = obs.registry()
        reg.count("train_logged_steps_total")
        reg.count("train_log_ahead_total", int(ahead))
        with obs.span("train.log", step=step, ahead=int(ahead)):
            with obs.span("train.sync", step=step):
                jax.block_until_ready(metrics["loss"])
            with obs.span("train.lr", step=step):
                lr = host_lr(self.cfg, self.steps_per_epoch, step - 1)
            with obs.span("train.fetch", step=step) as fetch:
                loss, grad_norm = jax.device_get(
                    (metrics["loss"], metrics["grad_norm"]))
                last = {"loss": float(loss),
                        "grad_norm": float(grad_norm)}
                arrays, routing = 2, {}
                if "routing" in metrics:
                    arrays += (len(metrics["routing"])
                               + len(logged.dropped))
                    routing = obs.observe_routing(metrics["routing"],
                                                  logged.dropped)
                fetch.set(arrays=arrays)
            with obs.span("train.emit", step=step):
                self.logger.log(
                    "train_step", step=step, epoch=logged.epoch,
                    lr=round(lr, 8),
                    utt_per_sec_per_chip=round(rate, 3),
                    **last, **routing)
                if self.tb is not None:
                    # The per-expert lists stay in the log line and
                    # the registry.
                    self.tb.scalars(
                        step, **last, lr=lr, utt_per_sec_per_chip=rate,
                        **{k: v for k, v in routing.items()
                           if np.isscalar(v)})
        return last

    def fit(self, epochs: Optional[int] = None) -> Dict[str, float]:
        """Run the epochs; returns the last logged step's loss and
        gradient norm (and the last evaluation's scores).

        The unguarded loop hands step k+1 over BEFORE it reads step k:
        with ``train.log_every`` the ``train_step`` line for step k is
        written after step k+1 has been dispatched, once the loop has
        blocked on step k's loss, so the log line, the next batch's
        prefetch and the dispatch run while the device works. At most
        one step is handed over beyond the one being read, and every
        event is still a completed step, in step order. The pending
        line is written (``drain``) before anything that relies on it:
        the end of an epoch and ``train.eval``, every checkpoint, the
        ``preempted`` event, the profiler's ``stop_trace``, and
        ``fit``'s return or an exception leaving the loop. Under the
        guardian the loop is synchronous (a step's metrics decide a
        rollback before the next dispatch), and steps that are not
        logged have no turn to hide."""
        cfg = self.cfg
        epochs = epochs if epochs is not None else cfg.train.epochs
        n_chips = self.mesh.devices.size
        thr = Throughput(n_chips)
        last = {}
        # The logged step whose line is not written yet.
        pending = None

        def drain(ahead: bool = False) -> None:
            nonlocal pending, last
            if pending is not None:
                logged, pending = pending, None
                last = self._log_step(logged, thr.rate_per_chip(), ahead)

        # Deterministic mid-epoch resume: the sampler is a pure function
        # of (seed, epoch), so skipping the batches already consumed
        # replays the exact original data order (SURVEY.md §5).
        steps_before = sum(self.pipeline.batches_per_epoch(e)
                           for e in range(self.start_epoch))
        # Host-side step counter, synced to the device once here: reading
        # state.step inside the loop would force a device->host sync
        # every step and stall the dispatch pipeline (the host must run
        # ahead of the device for input transfer to overlap compute).
        step = int(self.state.step)
        skip = max(step - steps_before, 0)
        profiling = False
        profile_end = (cfg.train.profile_start_step
                       + cfg.train.profile_steps)
        profile_done = False
        preempted = False
        # Guardian bookkeeping: ``consumed`` is the batch's ordinal in
        # the run's data stream — it keeps advancing through skips and
        # rollbacks (the stream only moves forward; recovery replays
        # nothing), which is what makes the surviving-batch list exact.
        consumed = step
        watchdog = None
        if self.guardian is not None:
            gcfg = self.guardian.cfg
            if gcfg.watchdog:
                from .resilience.guardian import StallWatchdog

                watchdog = StallWatchdog(
                    k=gcfg.watchdog_k, min_timeout_s=gcfg.watchdog_min_s,
                    poll_s=gcfg.watchdog_poll_s,
                    preempt=self.preempt).start()
            # Seed the last-good ring so the very first anomaly has a
            # rollback target.
            self.guardian.snapshot(step, self.state)
        try:
            for epoch in range(self.start_epoch, epochs):
                t_epoch = time.perf_counter()
                batches = iter(self.pipeline.epoch(epoch))
                # Deterministic resume: drop the already-consumed prefix
                # BEFORE the device-prefetch wrapper so skipped batches
                # never pay a transfer.
                while skip > 0 and next(batches, None) is not None:
                    skip -= 1
                # Double-buffered host->device prefetch: batch k+1's
                # shard/device_put dispatches while batch k's step runs,
                # taking the transfer off the step's critical path.
                from .data.pipeline import device_prefetch

                for sharded in device_prefetch(
                        batches,
                        put_fn=lambda b: shard_batch(
                            self.mesh, b,
                            time_sharded=cfg.train.sequence_parallel)):
                    # ">=" so a resume landing past profile_start_step
                    # still captures a window (of the remaining steps).
                    if (cfg.train.profile_dir and not profiling
                            and not profile_done
                            and step >= cfg.train.profile_start_step
                            and step < profile_end):
                        jax.profiler.start_trace(cfg.train.profile_dir)
                        profiling = True
                    spec = faults.inject("train.step")
                    if spec is not None and spec.kind == "nan_grad":
                        # Chaos: poison the device batch so this step's
                        # loss/gradients come out non-finite — the
                        # guarded step's gate (or, unguarded, the run's
                        # death) is what the training scenario of
                        # tests/test_resilience.py drives.
                        feats = sharded["features"]
                        sharded = dict(sharded, features=feats * jnp.asarray(
                            jnp.nan, feats.dtype))
                    t_step = time.perf_counter()
                    with obs.span("train.step", step=step):
                        with obs.span("train.dispatch", step=step):
                            if self.guardian is not None:
                                self.state, metrics = self.train_step(
                                    self.state, sharded,
                                    {"lr_scale":
                                     np.float32(self.guardian.lr_scale)})
                            else:
                                self.state, metrics = self.train_step(
                                    self.state, sharded)
                        if obs.tracer.enabled:
                            # The step's layer table, for a reader after
                            # the run: the new state stands for the
                            # donated one (same shapes and shardings).
                            obs.layers.watch(
                                "train_step", self.train_step,
                                (self.state, sharded) + (
                                    () if self.guardian is None else
                                    ({"lr_scale": np.float32(1)},)))
                            # Attribution without giving up the
                            # overlap: the traced loop blocks here on
                            # the step whose line is owed, the one
                            # BEFORE this, so the span still ends one
                            # device step after the last one did. A
                            # step whose line is not put off (the
                            # guardian's, an unlogged one) blocks on
                            # itself.
                            if pending is not None:
                                with obs.span("train.wait",
                                              step=pending.step - 1):
                                    jax.block_until_ready(
                                        pending.metrics["loss"])
                            elif (self.guardian is not None or
                                  (step + 1) % cfg.train.log_every):
                                with obs.span("train.wait", step=step):
                                    jax.block_until_ready(
                                        metrics["loss"])
                    # The host's turn for the step before, while the
                    # device runs this one.
                    drain(ahead=True)
                    if self.guardian is not None:
                        # observe_step reads the metrics (the device
                        # sync the guarded mode accepts), so the
                        # duration recorded here covers the whole step.
                        decision = self.guardian.observe_step(
                            step, consumed, metrics)
                        obs.registry().observe(
                            STEP_HIST, time.perf_counter() - t_step)
                        if watchdog is not None:
                            watchdog.heartbeat()
                        consumed += 1
                        if decision.action == "rollback":
                            rb_step, host_state = self.guardian.rollback(
                                decision.trigger)
                            self.state = jax.device_put(host_state,
                                                        self.state_sh)
                            step = rb_step
                            self.logger.log("guardian_rollback",
                                            step=step,
                                            trigger=decision.trigger)
                            continue
                        if decision.action == "skip":
                            # The on-device gate already kept the old
                            # state; the host step counter must not
                            # advance either.
                            continue
                    if "routing" in metrics:
                        # Every step's count of pairs that did not fit
                        # the expert layers' rows, read at the next
                        # sync: no step drops a pair unnoticed.
                        self._dropped.append(
                            metrics["routing"]["dropped"])
                    thr.update(len(sharded["feat_lens"]))
                    step += 1
                    if self.guardian is not None:
                        self.guardian.maybe_snapshot(step, self.state)
                    if profiling and step >= profile_end:
                        float(metrics["loss"])  # drain before closing trace
                        jax.profiler.stop_trace()
                        profiling = False
                        profile_done = True
                        self.logger.log("profile_saved",
                                        dir=cfg.train.profile_dir, step=step)
                    if step % cfg.train.log_every == 0:
                        # Its line is written once the next step has
                        # been handed over (or at a drain below); this
                        # step's dropped counter goes with it, so the
                        # fetch never reads a step still running.
                        pending = _LoggedStep(step, epoch, metrics,
                                              self._dropped)
                        self._dropped = []
                        if self.guardian is not None:
                            # Synchronous: the next dispatch waits for
                            # what this step's metrics decided.
                            drain()
                    if (cfg.train.checkpoint_every_steps and self.ckpt and
                            step % cfg.train.checkpoint_every_steps == 0):
                        drain()
                        self.save(epoch)
                    if self.preempt is not None \
                            and self.preempt.requested():
                        # Preemption grace window: persist at this step
                        # boundary and return cleanly. Saving the
                        # CURRENT epoch makes maybe_restore's
                        # consumed-prefix skip replay the remaining
                        # batches in the original order — the resumed
                        # run is bit-identical to an uninterrupted one.
                        drain()
                        if self.ckpt is not None:
                            with obs.span("train.emergency_checkpoint",
                                          step=step):
                                self.ckpt.wait()
                                if self.ckpt.latest_step() != step:
                                    self.save(epoch)
                                self.ckpt.wait()
                        self._check_dropless()
                        self.logger.log("preempted", step=step,
                                        epoch=epoch)
                        preempted = True
                        break
                if preempted:
                    break
                drain()
                self.logger.log("epoch_end", epoch=epoch,
                                seconds=round(time.perf_counter() - t_epoch, 1))
                if self.eval_pipeline is not None:
                    with obs.span("train.eval", epoch=epoch):
                        ev = self.evaluate()
                    self.logger.log("eval", epoch=epoch, **ev)
                    if self.tb is not None:
                        self.tb.scalars(int(self.state.step),
                                        wer=ev["wer"], cer=ev["cer"])
                    last.update(ev)
                self.save(epoch + 1)
        except BaseException:
            # Cleanup must not mask the in-flight exception; a cleanup
            # failure while unwinding is secondary, so only log it.
            try:
                # The step handed over before the exception is a
                # completed step all the same: its line is owed.
                drain()
            except Exception as e:
                self.logger.log("train_step_lost", error=repr(e))
            if watchdog is not None:
                try:
                    watchdog.stop()
                except Exception as e:
                    self.logger.log("watchdog_lost", error=repr(e))
            if profiling:
                try:
                    jax.profiler.stop_trace()
                except Exception as e:
                    self.logger.log("profile_lost", error=repr(e))
            if self.tb is not None:
                try:
                    self.tb.close()
                except Exception as e:
                    self.logger.log("tensorboard_lost", error=repr(e))
            raise
        else:
            # Clean exit: a stop_trace failure here is the primary
            # error — surface it instead of losing the profile quietly.
            if watchdog is not None:
                watchdog.stop()
            if profiling:
                jax.profiler.stop_trace()
                self.logger.log("profile_saved",
                                dir=cfg.train.profile_dir,
                                step=int(self.state.step))
            if self.tb is not None:
                self.tb.close()
        if self.ckpt is not None:
            self.ckpt.wait()
        if preempted:
            last = dict(last, preempted=True)
        if self.guardian is not None:
            last = dict(last, guardian=self.guardian.report())
        return last


def main(argv=None) -> None:
    import argparse

    from .config import (apply_overrides, get_config,
                     parse_cli_overrides)

    parser = argparse.ArgumentParser(prog="deepspeech_tpu.train")
    parser.add_argument("--config", default="ds2_small")
    parser.add_argument("--synthetic", type=int, default=0,
                        help="train on N synthetic utterances (no audio)")
    parser.add_argument("--log-file", default="")
    args, extra = parser.parse_known_args(argv)
    cfg = apply_overrides(get_config(args.config),
                          parse_cli_overrides(extra))

    from .parallel import initialize_distributed
    from .utils.cache import enable_compilation_cache

    enable_compilation_cache()
    initialize_distributed()
    logger = JsonlLogger(args.log_file or None)
    from .data.tokenizer import resolve_tokenizer

    old_vocab = cfg.model.vocab_size
    if args.synthetic:
        tokenizer, cfg = resolve_tokenizer(cfg, synthetic=True)
        pipeline = _SyntheticPipeline(cfg, args.synthetic)
    else:
        from .data import load_manifest

        utts = load_manifest(cfg.data.train_manifest,
                             cfg.data.min_duration_s,
                             cfg.data.max_duration_s)
        tokenizer, cfg = resolve_tokenizer(cfg, utterances=utts,
                                           for_training=True)
        pipeline = DataPipeline(cfg, tokenizer, utterances=utts)
    if cfg.model.vocab_size != old_vocab:
        logger.log("vocab_resize", preset=old_vocab,
                   tokenizer=cfg.model.vocab_size)
    eval_pipe = (DataPipeline(cfg, tokenizer, cfg.data.eval_manifest)
                 if cfg.data.eval_manifest else None)
    from .resilience import PreemptionGuard

    # SIGTERM (fleet preemption) -> emergency checkpoint + clean exit;
    # the next invocation's maybe_restore resumes bit-identically.
    with PreemptionGuard() as guard:
        trainer = Trainer(cfg, pipeline, tokenizer, eval_pipe, logger,
                          preempt=guard)
        trainer.maybe_restore()
        result = trainer.fit()
    logger.log("done", **{k: v for k, v in result.items()
                          if isinstance(v, (int, float))})


class _SyntheticPipeline:
    """Duck-typed DataPipeline over synthetic batches (tests, --synthetic)."""

    # Deterministic per-seed generation: every process holds the FULL
    # global batch, so replicated-batch mesh layouts are safe (see the
    # Trainer's process-major guard).
    provides_global_batches = True

    def __init__(self, cfg: Config, n_utts: int, frames: int = 0,
                 label_len: int = 12):
        self.cfg = cfg
        frames = frames or min(cfg.data.bucket_frames)
        bs = cfg.data.batch_size
        self.n_batches = max(n_utts // bs, 1)
        from .data.synthetic import synthetic_batch

        self.batches = [
            synthetic_batch(cfg, bs, frames, label_len, seed=i)[0]
            for i in range(self.n_batches)]

    def peek(self):
        return self.batches[0]

    def epoch(self, epoch_idx: int):
        return iter(self.batches)

    def eval_epoch(self):
        bs = len(self.batches[0]["feat_lens"])
        return iter([(b, bs) for b in self.batches])

    def batches_per_epoch(self, epoch_idx: int) -> int:
        return self.n_batches


if __name__ == "__main__":
    main()
