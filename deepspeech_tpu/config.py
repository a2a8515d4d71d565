"""Typed configuration for models, data, and training.

The five named presets mirror the workloads in ``BASELINE.json:6-12``
(the reference's `configs` list): DS2-small dev slice, full DS2 960h,
streaming lookahead variant, beam+LM decode, and Mandarin AISHELL-1.
The reference's flag system (SURVEY.md §2 component 17) is replaced by
plain frozen dataclasses + CLI overrides (``--key=value``).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class FeatureConfig:
    """Log-spectrogram frontend (SURVEY.md §2 component 1)."""

    sample_rate: int = 16000
    window_ms: float = 20.0
    stride_ms: float = 10.0
    # 320-sample window at 16 kHz -> rfft -> 161 bins, the DS2 layout.
    num_features: int = 161
    # Per-utterance mean/std normalization over valid frames.
    normalize: bool = True
    preemphasis: float = 0.97
    eps: float = 1e-6


@dataclass(frozen=True)
class ModelConfig:
    """DS2 model family (SURVEY.md §2 components 5-8, §3.4 shape flow)."""

    # Conv frontend: (time_kernel, freq_kernel, time_stride, freq_stride).
    conv_layers: Tuple[Tuple[int, int, int, int], ...] = (
        (11, 41, 2, 2),
        (11, 21, 1, 2),
    )
    conv_channels: Tuple[int, ...] = (32, 32)
    # RNN stack.
    rnn_layers: int = 3
    rnn_hidden: int = 800
    # "gru" | "lstm" | "lstmp" (LSTM with a recurrent projection,
    # Sak et al. arXiv:1402.1128: the carried/output state is the
    # ``rnn_proj``-wide projection of the cell output; unidirectional).
    rnn_type: str = "gru"
    # lstmp only: width of the recurrent projection, and layer
    # normalisation of the four gate pre-activations (learned gain and
    # bias per gate, none on the cell state).
    rnn_proj: int = 0
    rnn_layer_norm: bool = False
    bidirectional: bool = True
    # Streaming variant: unidirectional + lookahead conv over future frames.
    lookahead_context: int = 0  # 0 disables lookahead conv
    # Batch norm between RNN layers (sequence-wise, masked).
    rnn_batch_norm: bool = True
    vocab_size: int = 29  # EN: blank + a-z + space + apostrophe
    relu_clip: float = 20.0
    dtype: str = "bfloat16"  # compute dtype; params stay float32
    # Which RNN cell implementation drives the stack:
    #   "auto"   - fused Pallas cell on TPU, XLA scan elsewhere
    #   "xla"    - lax.scan over a jnp cell (reference / oracle path)
    #   "pallas" - fused Pallas cell (interpreter mode off-TPU)
    # Every cell of BENCHMARK.json runs "auto" = the fused cell
    # (`correct` checks rnn_impl_pallas), in the build
    # ops/scan_pallas.scan_route names from the call's shapes (ds2_full:
    # copied once into VMEM, `pinned`). Against the XLA scan on this
    # chip: measured for the LSTM-with-projection stack only (PERF.md
    # section 6, PR 26: 712.6 -> 307.9 ms a step); the GRU stack: not
    # measured.
    rnn_impl: str = "auto"
    # XLA-scan path only: >0 bounds the backward pass's per-step
    # residual memory to this many timesteps via chunked
    # rematerialization (models/rnn.py _scan_steps) — trades one extra
    # recurrence forward for O(T) -> O(chunk) residual HBM, unlocking
    # longer buckets / larger batches. 0 = plain scan. (The Pallas
    # cells recompute their backward internally already.)
    rnn_remat_chunk: int = 0
    # Pipeline parallelism (models/pipe_stack.py): >1 stages the
    # HOMOGENEOUS middle of the RNN stack (layers 1..rnn_layers-1, all
    # [B,T,H]->[B,T,H]) over the mesh's ``pipe`` axis as a GPipe
    # microbatch schedule — stage weights + optimizer state shard over
    # pipe, activations hop stage-to-stage via ppermute. Requires
    # (rnn_layers - 1) % pipeline_stages == 0 and a len-3
    # TrainConfig.mesh_shape whose pipe extent equals this. Layer 0
    # (conv-width input) and the head run data-parallel outside the
    # pipeline. 1 = off (the reference's DP-only layout).
    pipeline_stages: int = 1
    # Microbatches per step for the pipeline schedule; 0 = same as
    # pipeline_stages. Bubble fraction is (stages-1)/(microbatches+
    # stages-1), so more microbatches = better stage utilization.
    # batch_size must divide by it (strided split, train.py accum-style).
    pipeline_microbatches: int = 0
    # RNN-T family (train.objective="rnnt"): prediction-net width and
    # joint projection dim (models/transducer.py). The prediction net
    # is a GRU for rnn_type gru/lstm and ``rnnt_pred_layers`` LSTM
    # layers with the ``rnn_proj`` projection for rnn_type lstmp.
    rnnt_pred_hidden: int = 128
    rnnt_joint_dim: int = 256
    rnnt_pred_layers: int = 1
    rnnt_pred_embed: int = 64
    # The lstmp encoder (He et al. arXiv:1811.06621) has no conv
    # frontend: ``frame_stack`` adjacent feature frames are concatenated
    # into one input frame, and after encoder layer
    # ``time_reduction_layer`` (0 = never) every ``time_reduction``
    # adjacent outputs are concatenated into one frame.
    frame_stack: int = 1
    time_reduction_layer: int = 0
    time_reduction: int = 2
    # Decoder-only family (train.objective="lm"; models/lfm2.py): the
    # LFM2 block. ``frame_stack`` feature frames are projected to the
    # prefix of the decoder's sequence, the transcript follows it and
    # is trained by next-token cross-entropy over ``vocab_size`` ids
    # (this chip's slice of ``lfm_vocab_published``). Sizes carry the
    # published config's names behind the ``lfm_`` prefix.
    lfm_hidden: int = 2048
    # "conv" | "full_attention" | "sliding_attention" | "latent_attention"
    # | "ssm_attention" | "sparse_attention" | "linear_attention"
    lfm_layer_types: Tuple[str, ...] = ()
    lfm_dense_layers: int = 1        # leading layers with the dense FFN
    lfm_heads: int = 32
    lfm_kv_heads: int = 8
    lfm_ffn_dim: int = 11776         # intermediate_size
    lfm_expert_dim: int = 1536       # moe_intermediate_size
    lfm_conv_taps: int = 3           # conv_L_cache
    lfm_experts: int = 64            # the router's width, as published
    lfm_top_k: int = 4               # num_experts_per_tok
    lfm_rope_theta: float = 1e6
    lfm_norm_eps: float = 1e-5
    # The expert layer is told which experts it holds: ids
    # [expert_offset, expert_offset + experts_held) of ``lfm_experts``.
    # It routes over all of them and leaves out what the absent ones
    # would have added (one chip's share of an expert-parallel layer,
    # without its exchange).
    experts_held: int = 64
    expert_offset: int = 0
    # Static row capacity of the dropless dispatch, as a share of the
    # step's computed (position, choice) pairs; 0 = the worst case
    # (every pair lands here). A bound below the worst case is a
    # statement about the traffic: the step counts what would not fit
    # (``moe_dropped``, always 0 at capacity 0) and its high-water mark.
    moe_rows_bound: float = 0.0
    # Grouped matrix products of the expert layer: "auto" (Pallas
    # moe_gmm / moe_tgmm on a TPU, jax.lax.ragged_dot elsewhere) |
    # "xla" | "pallas".
    moe_impl: str = "auto"
    # Positions every sequence is right-padded to; 0 = the least that
    # holds a bucket: ceil(frames / frame_stack) + 1 + max_label_len,
    # rounded up to a multiple of 8.
    lfm_seq_positions: int = 0
    # The decoder-only shell serves a second family (models/axk1.py:
    # ``lfm_layer_types`` of "latent_attention"); what differs between
    # the two families' presets is stated here, not switched in code.
    # Output head: the embedding matrix transposed, or a matrix of its
    # own (``tie_word_embeddings`` false).
    lm_tied_head: bool = True
    # Selection rule of the router (``ops/moe.route``): the experts are
    # ``moe_groups`` runs of consecutive ids, a group scores its
    # maximum, only the ``moe_groups_kept`` best groups can be chosen
    # from (1 of 1: plain top-k); a seeded selection bias or none; the
    # normalised weights times ``moe_routed_scale``.
    moe_groups: int = 1
    moe_groups_kept: int = 1
    moe_select_bias: bool = True
    moe_routed_scale: float = 1.0
    # Experts every position passes through beside the routed ones
    # (``n_shared_experts``): one SwiGLU of that many expert widths.
    moe_shared_experts: int = 0
    # Latent attention (``model_type: axk1``): ranks of the query's and
    # the key/value's low-rank paths, and a head's three sizes (the
    # part of q.k without positions, the rotary part shared by all
    # heads' keys, the value).
    mla_q_rank: int = 1536
    mla_kv_rank: int = 512
    mla_nope_dim: int = 128
    mla_rope_dim: int = 64
    mla_v_dim: int = 128
    # YaRN scaling of the rotary frequencies (``rope_scaling``): factor
    # over the original context (factor 1: plain rotary), the ramp's
    # (beta_fast, beta_slow), and (mscale, mscale_all_dim).
    rope_yarn_factor: float = 1.0
    rope_yarn_original: int = 4096
    rope_yarn_betas: Tuple[float, float] = (32.0, 1.0)
    rope_yarn_mscales: Tuple[float, float] = (1.0, 1.0)
    # Manifold-constrained hyper-connections (``models/mhc.py``;
    # ``hc_mult`` and its siblings of ``model_type: xing4_0``): the
    # residual is ``hc_streams`` streams wide, each sub-layer reads a
    # learned mix of them and writes back through a post-mix and a
    # residual mix that ``hc_sinkhorn_iters`` Sinkhorn-Knopp rounds
    # (``hc_eps`` in each division) make doubly stochastic, from
    # exp(logits clamped to ``hc_res_clamp``). 1 stream: the plain
    # ``h + f(norm(h))``, and none of the rest is read.
    hc_streams: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    # Multi-token-prediction modules (``num_nextn_predict_layers``)
    # after the last layer: one more expert layer each, over the next
    # input's embedding and the model's last hidden state, that
    # predicts the token after next. Serving drafts with it inside the
    # greedy loop (``decode/lm_greedy.py``); 0: no module, no draft.
    lm_draft_layers: int = 0
    # Grouped-query attention as a family states it (``model_type:
    # afmoe``; every default is LFM2's reading, so its programs do not
    # move). A head's size where it is not ``lfm_hidden / lfm_heads``
    # (0); the window of a "sliding_attention" layer (position i sees
    # keys ``i - window < j <= i``; a "full_attention" layer sees all);
    # the layer kinds whose queries and keys are rotated (a kind left
    # out carries no positions); a sigmoid gate on the attention's
    # output, one value a head and channel, from a projection of the
    # layer's input (``gate_proj``).
    lfm_head_dim: int = 0
    lfm_window: int = 0
    lfm_rope_kinds: Tuple[str, ...] = ("full_attention",
                                       "sliding_attention")
    lfm_attn_gate: bool = False
    # Sandwich norms: a second RMSNorm on each sub-layer's OUTPUT,
    # ``h + post_norm(f(pre_norm(h)))``.
    lfm_post_norms: bool = False
    # ``mup_enabled``: the embedding (and the audio prefix, so that both
    # kinds of position enter at one scale) times sqrt(lfm_hidden).
    lfm_embed_scale: bool = False
    # Seeded gains of every RMSNorm: 1 + normal(std) (0: ones), so that
    # on seeded weights a dropped gain is seen.
    lfm_norm_gain_std: float = 0.0
    # The expert block as a second family states it (``model_name:
    # smallthinker``; every default is LFM2's reading): RMSNorm on each
    # head of q and k, or none; the router's scoring function
    # ("sigmoid": scores normalised over the chosen; "softmax": a
    # softmax over the chosen logits, ``moe_primary_router_apply_softmax``);
    # the gated experts' activation ("silu" | "relu"); and where the
    # router reads: the feed-forward's normed input, or
    # (``moe_route_pre_attn``) the layer's INPUT, before its norm and
    # its attention, the routing carried to the feed-forward.
    lfm_qk_norm: bool = True
    moe_score_func: str = "sigmoid"
    moe_expert_act: str = "silu"
    moe_route_pre_attn: bool = False
    # The hybrid block (``model_type: falcon_h1``; a layer of kind
    # "ssm_attention"): a Mamba-2 state-space mixer and grouped-query
    # attention side by side on ONE normed input, their outputs summed
    # into the residual. The mixer's sizes under the published names
    # (``mamba_d_ssm`` = ``ssm_heads`` x a head, ``mamba_d_state``,
    # ``mamba_n_groups``: the heads of a group share B and C,
    # ``mamba_d_conv`` taps of the depthwise convolution before it,
    # ``mamba_chunk_size`` positions a chunk of the sequence form).
    ssm_d_ssm: int = 0
    ssm_heads: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # muP multipliers, data of a preset under the published names (1:
    # absent from the program, not multiplied): on what enters the first
    # layer, on the logits, on attention's input, keys and output, on
    # the mixer's input, on the five segments of its projection (z, x,
    # B, C, dt) and on its output, on the dense feed-forward's gate
    # argument and on its output.
    mup_embedding: float = 1.0
    mup_lm_head: float = 1.0
    mup_attn_in: float = 1.0
    mup_key: float = 1.0
    mup_attn_out: float = 1.0
    mup_ssm_in: float = 1.0
    mup_ssm: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mup_ssm_out: float = 1.0
    mup_mlp: Tuple[float, float] = (1.0, 1.0)
    # ... and on every sub-layer's output before it joins the residual
    # (``scale_depth / sqrt(num_hidden_layers)`` of ``model_type:
    # minicpm_sala``, with the PUBLISHED depth).
    mup_residual: float = 1.0
    # The block selection of a layer of kind "sparse_attention"
    # (InfLLM-v2, the ``minicpm4`` mixer of ``model_type: minicpm_sala``):
    # past ``sparse_dense_len`` rows a query reads block 0
    # (``sparse_init_blocks``), the blocks of ``sparse_block`` rows that
    # hold its last ``sparse_window`` rows, and the ``sparse_topk`` best
    # of the rest, scored through keys pooled over ``sparse_kernel``
    # rows every ``sparse_stride`` (``models/lfm2.block_scores``).
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_block: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window: int = 2048
    sparse_dense_len: int = 8192
    # A layer of kind "linear_attention" (``lightning-attn``): ``lin_heads``
    # heads of ``lin_head_dim`` with one constant decay a head,
    # ``exp(-s_h (1 - l / (lin_depth - 1) + 1e-5))``, ``s_h = 2^(-8 h /
    # lin_heads)``, l the PUBLISHED index of the layer (``lin_layer_index``,
    # one entry a layer of ``lfm_layer_types``; entries of other kinds
    # are not read) and ``lin_depth`` the published depth; rotary
    # positions at ``lin_rope_theta``.
    lin_heads: int = 0
    lin_head_dim: int = 0
    lin_layer_index: Tuple[int, ...] = ()
    lin_depth: int = 0
    lin_rope_theta: float = 1e4

    @property
    def time_stride(self) -> int:
        s = self.frame_stack
        if self.time_reduction_layer > 0:
            s *= self.time_reduction
        for (_, _, ts, _) in self.conv_layers:
            s *= ts
        return s


@dataclass(frozen=True)
class DataConfig:
    """Manifest + SortaGrad bucketing (SURVEY.md §2 components 3-4)."""

    train_manifest: str = ""
    eval_manifest: str = ""
    # GLOBAL batch per step; sharded over the data mesh axis, so it must
    # be divisible by the data-axis size.
    batch_size: int = 32
    max_duration_s: float = 16.5
    min_duration_s: float = 0.3
    # Static bucket boundaries in *feature frames*; each bucket compiles one
    # executable (XLA static shapes). Buckets double as the padding spec.
    bucket_frames: Tuple[int, ...] = (400, 800, 1200, 1700)
    max_label_len: int = 256
    sortagrad: bool = True  # epoch 0 sorted by duration
    # Training-time waveform augmentation (gain + noise + small shift,
    # data/augment.py). Train epochs only; deterministic per
    # (shuffle_seed, epoch, utterance) so resume replays exactly.
    # Forces the numpy featurizer path (bypasses feature cache + native
    # loader — augmented audio must be featurized fresh each epoch).
    augment: bool = False
    # Opt-in feature-domain masking (SpecAugment-style time/freq
    # stripes, data/augment.py). Postdates the DS2 recipe — off by
    # default for reference fidelity; same (seed, epoch, utt)
    # determinism contract as ``augment``.
    spec_augment: bool = False
    shuffle_seed: int = 1234
    language: str = "en"  # "en" | "zh"
    # Tokenizer vocab file (one char/line). Required for "zh" unless the
    # inventory is derived from the training manifest's transcripts.
    vocab_path: str = ""
    # Use the native C++ loader (threaded wav->features, native/src) for
    # uncached .wav corpora; falls back to the numpy path automatically
    # when the library is unavailable or a file is not .wav.
    native_loader: bool = True
    # Corrupt-sample quarantine (data/pipeline.scrub_samples): samples
    # with non-finite features, empty labels, or labels longer than
    # their frames can carry are replaced by a healthy donor row
    # (shapes unchanged), counted, and written as a postmortem record
    # instead of poisoning the step.
    quarantine_corrupt: bool = True


@dataclass(frozen=True)
class TrainConfig:
    """Optimizer/schedule/loop (SURVEY.md §2 component 15)."""

    optimizer: str = "sgd"  # "sgd" | "adamw"
    learning_rate: float = 3e-4
    momentum: float = 0.99
    # adamw only; decays every leaf (no mask), so a preset with norm
    # gains keeps it 0.
    weight_decay: float = 0.0
    grad_clip_norm: float = 400.0
    lr_anneal: float = 1.1  # divide LR by this each epoch (DS2-era schedule)
    warmup_steps: int = 500
    epochs: int = 20
    log_every: int = 10
    eval_every_steps: int = 1000
    checkpoint_every_steps: int = 1000
    checkpoint_dir: str = "/tmp/deepspeech_tpu_ckpt"
    keep_checkpoints: int = 3
    seed: int = 0
    # Mesh shape: (data, model), or (data, pipe, model) when
    # ModelConfig.pipeline_stages > 1 (pipe extent must equal it).
    # data=0 means "all devices / rest"; model>1 shards the output
    # head / big FCs over the model axis.
    mesh_shape: Tuple[int, ...] = (0, 1)
    # Gradient accumulation: split each global batch into this many
    # microbatches inside the jitted step (lax.scan) and average the
    # grads — effective batch beyond HBM capacity. batch_size must be
    # divisible by accum_steps * data-axis size.
    accum_steps: int = 1
    # ZeRO-1: shard optimizer state (sgd trace / adamw mu+nu) over the
    # data mesh axis instead of replicating it — each data rank stores
    # and updates 1/data of the momentum buffers; XLA all-gathers the
    # param update where applied. Params stay replicated. Beyond the
    # reference (SURVEY §2 parallelism table: DP-only, no ZeRO).
    zero_opt_sharding: bool = False
    # "auto" (Pallas kernel on TPU, jnp oracle elsewhere) | "jnp" |
    # "pallas". On the TPU "auto" takes the Pallas kernel: 1.1 ms of an
    # 893 ms step in ds2_full.train_1chip (ledger PR 27,
    # `ctc_kernel_ms`); against the jnp oracle on this chip: not
    # measured.
    loss_impl: str = "auto"
    # Training objective / model family: "ctc" (the DS2 stack) or
    # "rnnt" (transducer: models/transducer.RNNTModel trained through
    # ops/transducer.rnnt_joint_loss, which never holds the
    # [B,T',U+1,V] lattice; greedy transducer eval, single process, no
    # sequence_parallel/pipeline); "lm" (decoder-only recogniser:
    # models/lfm2.LFM2ASR, projected audio frames as the prefix and
    # next-token cross-entropy over the transcript; single process, no
    # sequence_parallel/pipeline, no in-training eval yet).
    objective: str = "ctc"
    # Sequence-parallel training (parallel/seqpar.sp_loss): the TIME
    # axis of each batch shards over the mesh's data axis — conv halos
    # and recurrence/CTC-alpha carries relay via ppermute, so
    # activations, logits, and the loss recursion live [T/data] per
    # device. For long-utterance training whose activations exceed one
    # chip; gradients are exactly the offline ones. Batch rows are
    # replicated (time replaces batch as the parallel dimension), so
    # keep batch_size small. Excludes accum_steps>1, pipeline_stages>1,
    # explicit Pallas impls, and multi-process runs. Every
    # data.bucket_frames must divide by data_axis * time_stride.
    sequence_parallel: bool = False
    # TensorBoard scalar curves (loss/grad_norm/lr/utt_per_sec + eval
    # WER/CER); empty disables the writer.
    tensorboard_dir: str = ""
    # Profiling (SURVEY.md §5 tracing): when profile_dir is set, steps
    # [profile_start_step, profile_start_step + profile_steps) of the
    # run are captured with jax.profiler (view in TensorBoard).
    profile_dir: str = ""
    profile_start_step: int = 10
    profile_steps: int = 3
    # Self-healing training (resilience/guardian.py): the jitted step
    # additionally computes update-norm and gates the state transition
    # on loss/grad/update finiteness (a bad step is a bit-exact no-op),
    # and Trainer.fit runs the skip/backoff/rollback policy ladder plus
    # the stall watchdog. Knobs beyond on/off ride the DS2_GUARDIAN env
    # (see resilience.GuardianConfig); DS2_GUARDIAN also enables the
    # guardian when this flag is off.
    guardian: bool = False


@dataclass(frozen=True)
class DecodeConfig:
    """Greedy/beam decoding + LM rescoring (SURVEY.md §2 components 10-12)."""

    # "greedy": on-device argmax+collapse.
    # "beam": on-device prefix beam search; optional LM rescoring of the
    #   final n-best on host (the TPU-native path, SURVEY.md §3.2).
    # "beam_fused": host prefix beam search with per-word LM shallow
    #   fusion (the reference's C++ decoder semantics; slower).
    # "beam_fused_device": on-device beam search with char-level LM
    #   shallow fusion via a dense backoff-resolved table gathered
    #   inside the scan (exact for char LMs, e.g. Mandarin); needs an
    #   ARPA text LM.
    # "streaming": greedy through the chunked streaming engine
    #   (lookahead variant only; equals offline greedy).
    # "sp_greedy": greedy through the sequence-parallel engine
    #   (parallel/seqpar.py): the time axis shards over every device so
    #   one long recording decodes with [T/n_devices] activations per
    #   chip — for offline BIDIRECTIONAL models on audio too long for
    #   one device; equals offline greedy exactly.
    # "sp_beam": prefix beam search over the same time-sharded engine —
    #   the beam state relays shard-to-shard (exact: chunked beam ==
    #   offline beam), optional on-device LM fusion, host n-best
    #   rescoring when decode.lm_path is set without fusion.
    # "rnnt_greedy"/"rnnt_beam": transducer checkpoints
    #   (train.objective="rnnt"; models/transducer.py) — greedy or
    #   prefix-merged beam (beam_width/nbest apply; no LM path).
    # "lm_greedy": decoder-only checkpoints (train.objective="lm";
    #   decode/lm_greedy.py) — prefill the audio prefix into a cache,
    #   then one on-device loop of argmax steps through the cache.
    mode: str = "greedy"
    # Feature frames per streaming chunk (decode.mode=streaming).
    chunk_frames: int = 64
    beam_width: int = 64
    # On-device search considers only the top-k vocab symbols per frame
    # (static-shape vocab pruning; use vocab_size-1 for exact search).
    prune_top_k: int = 40
    # How many beams per utterance go to LM rescoring.
    nbest: int = 8
    # Shallow-fusion / rescoring weights: score + alpha*logP_LM + beta*|words|
    lm_path: str = ""  # ARPA or KenLM binary; empty disables LM
    lm_alpha: float = 0.5
    lm_beta: float = 1.0
    prune_log_prob: float = -12.0  # host fusion: per-step vocab threshold
    # beam_fused_device: LM context chars k-1 baked into the dense
    # fusion table (memory V^k); 0 = auto (LM order - 1, capped).
    device_lm_context: int = 0
    # Device fusion table layout: "dense" ([V^k, V] gather — fastest,
    # memory exponential in k), "hashed" (open-addressing n-gram tables
    # probed on device — O(#ngrams) memory, unlocks trigram+ fusion at
    # Mandarin vocab sizes), "auto" (dense while it fits the entry
    # budget at the requested context, hashed when a longer context is
    # wanted than dense can hold).
    device_lm_impl: str = "auto"
    # Host beam-search implementation for "beam_fused":
    #   "auto"   - C++ decoder (native/src/beam.cc) when it builds,
    #              else the Python oracle;
    #   "native" - require the C++ decoder;
    #   "python" - force the Python oracle.
    host_impl: str = "auto"
    # On-device prefix-merge strategy (decode/beam.py _resolve_merge):
    # "auto" follows the measured W<=32 crossover on every backend
    # ("match" for small beams, "sort" above — the only width with
    # hardware data); "sort"/"match" force one.
    merge_impl: str = "auto"
    # Greedy/streaming modes: emit per-character timestamps from the
    # CTC argmax alignment (the DS2-era timing proxy) — each utt event
    # gains "times": [[char, start_ms, end_ms], ...].
    timestamps: bool = False
    # lm_greedy: utterances prefilled at once (the prefill program's
    # batch; a call's rows are a multiple of it or fewer), and whether
    # a stream decodes on past the end id up to its ``max_tokens`` (a
    # serving benchmark on seeded weights, whose end id means nothing).
    lm_prefill_rows: int = 32
    lm_ignore_end: bool = False
    # lm_greedy: streams whose every step's logits and router outputs a
    # call gives out (a check reads them through the serving
    # executable): [rows, steps, vocabulary] float32 in every call.
    lm_watch_rows: int = 8


@dataclass(frozen=True)
class Config:
    features: FeatureConfig = field(default_factory=FeatureConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    decode: DecodeConfig = field(default_factory=DecodeConfig)
    name: str = "ds2_small"


def _replace(cfg, **kw):
    return dataclasses.replace(cfg, **kw)


# ---------------------------------------------------------------------------
# Presets: one per workload in BASELINE.json configs list.
# ---------------------------------------------------------------------------

def ds2_small() -> Config:
    """DS2-small: 2 conv + 3 BiGRU (BASELINE.json:7)."""
    return Config(name="ds2_small")


def ds2_full() -> Config:
    """Full DS2: 2 conv + 7 BiGRU + BN, 960h DP training (BASELINE.json:8)."""
    c = Config(name="ds2_full")
    return _replace(
        c,
        model=_replace(c.model, rnn_layers=7, rnn_hidden=1760),
    )


def ds2_streaming() -> Config:
    """Streaming: unidirectional GRU + lookahead conv (BASELINE.json:9)."""
    c = Config(name="ds2_streaming")
    return _replace(
        c,
        model=_replace(
            c.model,
            rnn_layers=5,
            rnn_hidden=800,
            bidirectional=False,
            lookahead_context=20,
        ),
    )


def ds2_beam_lm() -> Config:
    """Beam-search decode with external n-gram rescoring (BASELINE.json:10)."""
    c = ds2_small()
    return _replace(
        c,
        name="ds2_beam_lm",
        decode=_replace(c.decode, mode="beam", beam_width=128),
    )


def aishell() -> Config:
    """Mandarin character CTC, AISHELL-1 (BASELINE.json:11).

    Big vocab (~4.3k chars + blank) stresses the CTC kernel's V dimension
    and motivates model-axis sharding of the output head.

    On-device beam search at this scale (B=8, W=128): the merge's cost
    grows with prune_top_k; its time on this chip is not measured
    (PERF.md section 7 keeps `aishell.decode_beam_w128` as an open
    cell). The default prune_top_k=40 keeps decode exactness headroom;
    20 suffices when the top-20 symbols per frame do.
    """
    c = Config(name="aishell")
    return _replace(
        c,
        model=_replace(c.model, vocab_size=4336),
        data=_replace(c.data, language="zh"),
    )


def dev_slice() -> Config:
    """100-utterance dev-clean overfit slice (BASELINE.json:7); e2e gate."""
    c = ds2_small()
    return _replace(
        c,
        name="dev_slice",
        data=_replace(c.data, batch_size=8, bucket_frames=(400, 800, 1700)),
        train=_replace(c.train, epochs=50, learning_rate=1e-3,
                       optimizer="adamw"),
    )


def rnnt_he2019() -> Config:
    """The streaming RNN-T of He et al. 2019 (arXiv:1811.06621, model
    section) at its published widths: 8 unidirectional LSTM-2048 layers
    with a 640 projection and layer normalisation, time reduction 2
    after layer 2, a 2 x LSTM-2048/640 prediction net over a 128-wide
    embedding, joint 640, 4096 word-pieces (blank among them); about
    122 M parameters. The front end is this repo's 161-bin
    log-spectrogram at 10 ms, three frames stacked to 483 inputs at
    30 ms (the paper's is log-mel); ``benchmark/configs/
    rnnt_he2019.json`` lists every such reading."""
    c = Config(name="rnnt_he2019")
    return _replace(
        c,
        model=_replace(
            c.model, conv_layers=(), conv_channels=(),
            rnn_type="lstmp", rnn_layers=8, rnn_hidden=2048,
            rnn_proj=640, rnn_layer_norm=True, bidirectional=False,
            rnn_batch_norm=False, frame_stack=3,
            time_reduction_layer=2, time_reduction=2,
            rnnt_pred_layers=2, rnnt_pred_hidden=2048,
            rnnt_pred_embed=128, rnnt_joint_dim=640, vocab_size=4096),
        data=_replace(c.data, batch_size=64, max_label_len=64),
        train=_replace(c.train, objective="rnnt"),
        decode=_replace(c.decode, mode="rnnt_greedy"),
    )


LFM2_PERIOD = ("full_attention", "conv", "conv", "conv")


def lfm2_24b_a2b() -> Config:
    """One chip's share of LFM2-24B-A2B (``model_type: lfm2_moe``,
    https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json)
    as a decoder-only speech recogniser, every width as published:
    hidden 2048, 32 query / 8 key-value heads of 64, 3-tap gated short
    convolutions, dense SwiGLU 11776, 64 routed experts of 1536, top-4
    with a selection bias, sigmoid scores normalised over the chosen.
    The stated deployment divides each layer over 8 chips: 8 of the 64
    experts and 8192 of the 65536 vocabulary rows live here, the rest
    is replicated. Depth is cut to one leading dense ``conv`` layer and
    one whole period (attention, conv, conv, conv) of sparse layers.
    ``benchmark/configs/lfm2_24b_a2b.json`` has the published keys
    beside these and every reading that is this repo's own (the audio
    prefix, the tied head, AdamW's settings)."""
    c = Config(name="lfm2_24b_a2b")
    return _replace(
        c,
        model=_replace(
            c.model, conv_layers=(), conv_channels=(), rnn_layers=0,
            bidirectional=False, rnn_batch_norm=False, frame_stack=8,
            vocab_size=8192, lfm_layer_types=("conv",) + LFM2_PERIOD,
            lfm_dense_layers=1, experts_held=8, expert_offset=0,
            moe_rows_bound=0.21875),
        data=_replace(c.data, batch_size=128, bucket_frames=(1696,),
                      max_label_len=64),
        train=_replace(c.train, objective="lm", optimizer="adamw",
                       learning_rate=1e-4, weight_decay=0.0,
                       grad_clip_norm=1.0, warmup_steps=100),
    )


def ax_k1() -> Config:
    """One chip's share of A.X-K1 (``model_type: axk1``,
    https://huggingface.co/skt/A.X-K1/blob/main/config.json) as a
    decoder-only speech recogniser that is SERVED (``decode.mode=
    "lm_greedy"``), every width as published: hidden 7168, 64 heads of
    latent attention (query rank 1536, key/value rank 512, head sizes
    128 | 64 | 128, YaRN factor 32 over 4096), dense SwiGLU 18432 in
    the leading layer, then 192 sigmoid-scored routed experts of 2048,
    top-8 out of the 4 best of 8 groups, weights normalised times 2.5,
    one shared expert, an untied head. The stated deployment divides
    each layer over 16 chips: 12 of the 192 experts and 20,480 of the
    163,840 vocabulary rows live here, the rest is replicated. Depth is
    cut to the leading dense layer and 7 expert layers.
    ``benchmark/configs/ax_k1.json`` has the published keys beside
    these and every reading that is this repo's own."""
    c = Config(name="ax_k1")
    return _replace(
        c,
        model=_replace(
            c.model, conv_layers=(), conv_channels=(), rnn_layers=0,
            bidirectional=False, rnn_batch_norm=False, frame_stack=8,
            vocab_size=20480, lfm_hidden=7168,
            lfm_layer_types=("latent_attention",) * 8,
            lfm_dense_layers=1, lfm_heads=64, lfm_kv_heads=64,
            lfm_ffn_dim=18432, lfm_expert_dim=2048, lfm_experts=192,
            lfm_top_k=8, lfm_rope_theta=1e4, lfm_norm_eps=1e-6,
            experts_held=12, expert_offset=0, moe_rows_bound=0.25,
            lfm_seq_positions=288, lm_tied_head=False, moe_groups=8,
            moe_groups_kept=4, moe_select_bias=False,
            moe_routed_scale=2.5, moe_shared_experts=1,
            rope_yarn_factor=32.0),
        data=_replace(c.data, batch_size=256, bucket_frames=(1696,),
                      max_label_len=64),
        train=_replace(c.train, objective="lm", optimizer="adamw",
                       learning_rate=1e-4, weight_decay=0.0,
                       grad_clip_norm=1.0, warmup_steps=100),
        decode=_replace(c.decode, mode="lm_greedy"),
    )


def xing4_29b_a4b() -> Config:
    """Xing4.0-29B-A4B (``model_type: xing4_0``,
    https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json)
    as a decoder-only speech recogniser that is SERVED
    (``decode.mode="lm_greedy"``), every width as published: hidden
    3584 in FOUR residual streams mixed by manifold-constrained
    hyper-connections (20 Sinkhorn rounds), 32 heads of latent
    attention (query rank 768, key/value rank 512, head sizes 128 | 64
    | 128, YaRN factor 64 over 4096), dense SwiGLU 9216 in the leading
    layer, then 64 sigmoid-scored routed experts of 1024, top-4 by
    score + selection bias, weights normalised times 2, one shared
    expert, an untied head over all 131,072 ids, and one
    multi-token-prediction module that drafts inside the greedy loop.
    One chip holds EVERY expert and the whole vocabulary; depth alone
    is cut, to the leading dense layer and 6 expert layers (plus the
    module). ``benchmark/configs/xing4_29b_a4b.json`` has the published
    keys beside these and every reading that is this repo's own."""
    c = Config(name="xing4_29b_a4b")
    return _replace(
        c,
        model=_replace(
            c.model, conv_layers=(), conv_channels=(), rnn_layers=0,
            bidirectional=False, rnn_batch_norm=False, frame_stack=8,
            vocab_size=131072, lfm_hidden=3584,
            lfm_layer_types=("latent_attention",) * 7,
            lfm_dense_layers=1, lfm_heads=32, lfm_kv_heads=32,
            lfm_ffn_dim=9216, lfm_expert_dim=1024, lfm_experts=64,
            lfm_top_k=4, lfm_rope_theta=1e4, lfm_norm_eps=1e-6,
            experts_held=64, expert_offset=0, moe_rows_bound=0.0,
            lfm_seq_positions=288, lm_tied_head=False, moe_groups=1,
            moe_groups_kept=1, moe_select_bias=True,
            moe_routed_scale=2.0, moe_shared_experts=1,
            mla_q_rank=768, rope_yarn_factor=64.0, hc_streams=4,
            hc_sinkhorn_iters=20, hc_eps=1e-6,
            hc_res_clamp=(-30.0, 30.0), lm_draft_layers=1),
        data=_replace(c.data, batch_size=256, bucket_frames=(1696,),
                      max_label_len=64),
        train=_replace(c.train, objective="lm", optimizer="adamw",
                       learning_rate=1e-4, weight_decay=0.0,
                       grad_clip_norm=1.0, warmup_steps=100),
        decode=_replace(c.decode, mode="lm_greedy"),
    )


TRINITY_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)


def trinity_large() -> Config:
    """One chip's share of Trinity-Large-Preview (``model_type: afmoe``,
    https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/config.json)
    as a decoder-only speech recogniser that is SERVED
    (``decode.mode="lm_greedy"``) on recordings of minutes, every width
    as published: hidden 3072, 48 query / 8 key-value heads of 128 with
    a sigmoid output gate and RMSNorm on each head of q and k, three
    sliding-window layers of 4096 (rotary, theta 10000) to one global
    layer WITHOUT positions, four norms a layer, dense SwiGLU 12288 in
    the leading layer, then 256 sigmoid-scored routed experts of 3072,
    top-4 by score + selection bias, weights normalised times 2.448, one
    shared expert, an untied head, the embedding times sqrt(3072). The
    stated deployment divides each layer over 8 chips: 32 of the 256
    experts and 25,024 of the 200,192 vocabulary rows live here, the
    rest is replicated. Depth is cut to one leading dense layer
    (sliding) and one whole period of sparse layers. The cache is a ring
    of 4096 rows for a sliding layer and ``lfm_seq_positions`` rows for
    the global one. ``benchmark/configs/trinity_large.json`` has the
    published keys beside these and every reading that is this repo's
    own."""
    c = Config(name="trinity_large")
    return _replace(
        c,
        model=_replace(
            c.model, conv_layers=(), conv_channels=(), rnn_layers=0,
            bidirectional=False, rnn_batch_norm=False, frame_stack=8,
            vocab_size=25024, lfm_hidden=3072,
            lfm_layer_types=("sliding_attention",) + TRINITY_PERIOD,
            lfm_dense_layers=1, lfm_heads=48, lfm_kv_heads=8,
            lfm_head_dim=128, lfm_window=4096,
            lfm_rope_kinds=("sliding_attention",), lfm_attn_gate=True,
            lfm_post_norms=True, lfm_embed_scale=True,
            lfm_norm_gain_std=0.1, lfm_ffn_dim=12288,
            lfm_expert_dim=3072, lfm_experts=256, lfm_top_k=4,
            lfm_rope_theta=1e4, lfm_norm_eps=1e-5, experts_held=32,
            expert_offset=0, moe_rows_bound=0.25,
            lfm_seq_positions=6784, lm_tied_head=False, moe_groups=1,
            moe_groups_kept=1, moe_select_bias=True,
            moe_routed_scale=2.448, moe_shared_experts=1),
        data=_replace(c.data, batch_size=16, bucket_frames=(42000,),
                      max_label_len=1520),
        train=_replace(c.train, objective="lm", optimizer="adamw",
                       learning_rate=1e-4, weight_decay=0.0,
                       grad_clip_norm=1.0, warmup_steps=100),
        decode=_replace(c.decode, mode="lm_greedy", lm_prefill_rows=2,
                        lm_watch_rows=2),
    )


SMALLTHINKER_PERIOD = ("full_attention",) + ("sliding_attention",) * 3


def smallthinker_21b_a3b() -> Config:
    """One chip's share of SmallThinker-21BA3B-Instruct (``model_name:
    smallthinker_21b_instruct``,
    https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json)
    as a decoder-only speech recogniser that is TRAINED on recordings
    of minutes, every width as published: hidden 2560, 28 query / 4
    key-value heads of 128 without q/k norms, one global layer WITHOUT
    positions to three sliding-window layers of 4096 (rotary, theta
    1.5e6), two norms a layer, 64 routed experts of 768 in every layer,
    top-6 by the router's logits, which it reads from the layer's INPUT
    before attention, weights a softmax over the chosen six, gated-ReLU
    experts, no shared expert, an untied head. The stated deployment
    divides each layer over 4 chips: 16 of the 64 experts and 37,984 of
    the 151,936 vocabulary rows live here, the rest is replicated.
    Depth is cut to one whole period (global, sliding, sliding,
    sliding). ``benchmark/configs/smallthinker_21b_a3b.json`` has the
    published keys beside these and every reading that is this repo's
    own."""
    c = Config(name="smallthinker_21b_a3b")
    return _replace(
        c,
        model=_replace(
            c.model, conv_layers=(), conv_channels=(), rnn_layers=0,
            bidirectional=False, rnn_batch_norm=False, frame_stack=8,
            vocab_size=37984, lfm_hidden=2560,
            lfm_layer_types=SMALLTHINKER_PERIOD, lfm_dense_layers=0,
            lfm_heads=28, lfm_kv_heads=4, lfm_head_dim=128,
            lfm_window=4096, lfm_rope_kinds=("sliding_attention",),
            lfm_qk_norm=False, lfm_expert_dim=768, lfm_experts=64,
            lfm_top_k=6, lfm_rope_theta=1.5e6, lfm_norm_eps=1e-6,
            experts_held=16, expert_offset=0, moe_rows_bound=0.375,
            lfm_seq_positions=6784, lm_tied_head=False,
            moe_select_bias=False, moe_routed_scale=1.0,
            moe_shared_experts=0, moe_score_func="softmax",
            moe_expert_act="relu", moe_route_pre_attn=True),
        data=_replace(c.data, batch_size=4, bucket_frames=(42000,),
                      max_label_len=1520),
        train=_replace(c.train, objective="lm", optimizer="adamw",
                       learning_rate=1e-4, weight_decay=0.0,
                       grad_clip_norm=1.0, warmup_steps=100),
    )


def falcon_h1_34b() -> Config:
    """One pipeline stage of Falcon-H1-34B-Instruct (``model_type:
    falcon_h1``,
    https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json)
    as a decoder-only speech recogniser that is SERVED
    (``decode.mode="lm_greedy"``), every width as published: hidden
    5120; in every layer, under one norm, a Mamba-2 mixer (32 heads of
    128, state 256, 2 groups, a 4-tap convolution with bias, gated
    RMSNorm over each group) beside 20 query / 4 key-value heads of 128
    (rotary, theta 1e11, no q/k norm), summed; a dense gated MLP of
    21,504; fourteen muP multipliers; the whole vocabulary of 261,120,
    untied. No chips share a layer: depth is cut to one stage of 6 of
    the 72 layers. The cache of a layer is keys, values, the mixer's
    float32 state and the convolution's last three inputs.
    ``benchmark/configs/falcon_h1_34b.json`` has the published keys
    beside these and every reading that is this repo's own."""
    c = Config(name="falcon_h1_34b")
    return _replace(
        c,
        model=_replace(
            c.model, conv_layers=(), conv_channels=(), rnn_layers=0,
            bidirectional=False, rnn_batch_norm=False, frame_stack=8,
            vocab_size=261120, lfm_hidden=5120,
            lfm_layer_types=("ssm_attention",) * 6, lfm_dense_layers=6,
            lfm_heads=20, lfm_kv_heads=4, lfm_head_dim=128,
            lfm_rope_kinds=("ssm_attention",), lfm_qk_norm=False,
            lfm_norm_gain_std=0.1, lfm_ffn_dim=21504,
            lfm_rope_theta=1e11, lfm_norm_eps=1e-5,
            lfm_seq_positions=288, lm_tied_head=False,
            ssm_d_ssm=4096, ssm_heads=32, ssm_state=256, ssm_groups=2,
            ssm_conv=4, ssm_chunk=128, mup_embedding=5.656854249492381,
            mup_lm_head=0.0078125, mup_attn_in=1.0,
            mup_key=0.011048543456039804, mup_attn_out=0.0375,
            mup_ssm_in=0.25,
            mup_ssm=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
            mup_ssm_out=0.08838834764831845,
            mup_mlp=(0.1767766952966369, 0.011160714285714284)),
        data=_replace(c.data, batch_size=128, bucket_frames=(1696,),
                      max_label_len=64),
        train=_replace(c.train, objective="lm", optimizer="adamw",
                       learning_rate=1e-4, weight_decay=0.0,
                       grad_clip_norm=1.0, warmup_steps=100),
        decode=_replace(c.decode, mode="lm_greedy", lm_prefill_rows=32,
                        lm_watch_rows=2),
    )


SALA_PERIOD = ("sparse_attention",) + ("linear_attention",) * 3


def minicpm_sala() -> Config:
    """The first pipeline stage of MiniCPM-SALA (``model_type:
    minicpm_sala``,
    https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json)
    as a decoder-only speech recogniser that is SERVED
    (``decode.mode="lm_greedy"``) on recordings of 15-20 minutes, every
    width as published: hidden 4096; published layers 0-3, one
    ``minicpm4`` mixer (32 query / 2 key-value heads of 128 without
    positions, q/k norm, a sigmoid output gate; past 8,192 rows a query
    reads a SELECTION of the cache's blocks of 64: the first, the 32
    that hold its last 2,048 rows and the 64 best of the rest) to three
    ``lightning-attn`` mixers (32 heads of 128, rotary, q/k norm, one
    constant decay a head and published layer, an output norm and
    gate); dense SwiGLU 16,384; muP: embeddings times 12, every
    sub-layer's output times 1.4 / sqrt(32), logits over 16; the whole
    vocabulary of 73,448, untied. No chips share a layer. The cache of
    a sparse layer is keys, values and POOLED keys, of a linear layer
    the float32 state alone. ``benchmark/configs/minicpm_sala.json``
    has the published keys beside these and every reading that is this
    repo's own."""
    c = Config(name="minicpm_sala")
    return _replace(
        c,
        model=_replace(
            c.model, conv_layers=(), conv_channels=(), rnn_layers=0,
            bidirectional=False, rnn_batch_norm=False, frame_stack=8,
            vocab_size=73448, lfm_hidden=4096,
            lfm_layer_types=SALA_PERIOD, lfm_dense_layers=4,
            lfm_heads=32, lfm_kv_heads=2, lfm_head_dim=128,
            lfm_rope_kinds=(), lfm_qk_norm=True, lfm_attn_gate=True,
            lfm_norm_gain_std=0.1, lfm_ffn_dim=16384,
            lfm_rope_theta=1e4, lfm_norm_eps=1e-6,
            lfm_seq_positions=19328, lm_tied_head=False,
            mup_embedding=12.0, mup_lm_head=0.0625,
            mup_residual=0.2474873734152916,
            sparse_kernel=32, sparse_stride=16, sparse_block=64,
            sparse_topk=64, sparse_init_blocks=1, sparse_window=2048,
            sparse_dense_len=8192, lin_heads=32, lin_head_dim=128,
            lin_layer_index=(0, 1, 2, 3), lin_depth=32,
            lin_rope_theta=1e4),
        data=_replace(c.data, batch_size=32, bucket_frames=(120000,),
                      max_label_len=4320),
        train=_replace(c.train, objective="lm", optimizer="adamw",
                       learning_rate=1e-4, weight_decay=0.0,
                       grad_clip_norm=1.0, warmup_steps=100),
        decode=_replace(c.decode, mode="lm_greedy", lm_prefill_rows=2,
                        lm_watch_rows=1),
    )


PRESETS = {
    "ds2_small": ds2_small,
    "ds2_full": ds2_full,
    "ds2_streaming": ds2_streaming,
    "ds2_beam_lm": ds2_beam_lm,
    "aishell": aishell,
    "dev_slice": dev_slice,
    "rnnt_he2019": rnnt_he2019,
    "lfm2_24b_a2b": lfm2_24b_a2b,
    "ax_k1": ax_k1,
    "xing4_29b_a4b": xing4_29b_a4b,
    "trinity_large": trinity_large,
    "smallthinker_21b_a3b": smallthinker_21b_a3b,
    "falcon_h1_34b": falcon_h1_34b,
    "minicpm_sala": minicpm_sala,
}


def get_config(name: str) -> Config:
    if name not in PRESETS:
        raise KeyError(f"unknown preset {name!r}; have {sorted(PRESETS)}")
    return PRESETS[name]()


def _coerce(value, template):
    """Parse ``value`` (possibly a CLI string) to the type of ``template``."""
    if value is None or template is None:
        return value
    if isinstance(value, type(template)) and not isinstance(template, bool):
        return value
    if isinstance(template, bool):
        if isinstance(value, bool):
            return value
        s = str(value).strip().lower()
        if s in ("1", "true", "yes", "on"):
            return True
        if s in ("0", "false", "no", "off"):
            return False
        raise ValueError(f"cannot parse {value!r} as bool")
    if isinstance(template, tuple):
        if isinstance(value, (list, tuple)):
            items = value
        else:
            items = [p for p in str(value).split(",") if p.strip()]
        elem = template[0] if template else str
        return tuple(type(elem)(p) for p in items)
    return type(template)(value)


def parse_cli_overrides(extra) -> dict:
    """``--section.key=value`` leftovers from parse_known_args -> dict
    for apply_overrides. One implementation for every CLI entry point
    (train / infer / serve)."""
    overrides = {}
    for item in extra:
        if not item.startswith("--") or "=" not in item:
            raise SystemExit(f"unrecognized arg {item!r}")
        k, v = item[2:].split("=", 1)
        overrides[k] = v
    return overrides


def apply_overrides(cfg: Config, overrides: dict) -> Config:
    """Apply dotted-key overrides, e.g. {"train.learning_rate": "1e-4"}.

    Values may be strings (as they arrive from --key=value CLI flags);
    they are parsed to the field's existing type, including bools
    ("false" -> False) and comma-separated tuples ("400,800" -> (400, 800)).
    """
    for key, value in overrides.items():
        parts = key.split(".")
        if len(parts) == 1:
            cfg = _replace(cfg, **{parts[0]: _coerce(value, getattr(cfg, parts[0]))})
            continue
        if len(parts) != 2:
            raise KeyError(f"override key {key!r} must be section.field")
        section = getattr(cfg, parts[0])
        value = _coerce(value, getattr(section, parts[1]))
        cfg = _replace(cfg, **{parts[0]: _replace(section, **{parts[1]: value})})
    return cfg
