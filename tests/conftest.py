"""Test harness config: run everything on a virtual 8-device CPU mesh.

SURVEY.md §4.5: multi-chip logic is tested without a cluster via
``--xla_force_host_platform_device_count=8``. The platform is pinned
to cpu BEFORE any backend initializes, whatever the environment
selects, so the suite is hermetic, fast, and 8-way — and never reaches
for a chip some other process may hold.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import dataclasses  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

import pytest  # noqa: E402

from scenario import EDGES, NF  # noqa: E402


def _init_variables(cfg):
    import jax.numpy as jnp

    from deepspeech_tpu.models import create_model

    variables = create_model(cfg.model).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64, NF), jnp.float32),
        jnp.full((1,), 64, jnp.int32), train=False)
    return variables["params"], variables.get("batch_stats", {})


@pytest.fixture(scope="session")
def tiny_offline():
    """``dev_slice`` cut to one GRU-32 layer, greedy decode: ``cfg``,
    ``tok``, ``params``, ``stats`` and ``inferencer(**kw)``, which
    builds a fresh engine over the same weights."""
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.infer import Inferencer

    cfg = get_config("dev_slice")
    cfg = dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, rnn_hidden=32, rnn_layers=1,
                                  conv_channels=(4, 4), dtype="float32"),
        data=dataclasses.replace(cfg.data, bucket_frames=EDGES,
                                 batch_size=4),
        features=dataclasses.replace(cfg.features, num_features=NF),
        decode=dataclasses.replace(cfg.decode, mode="greedy"))
    tok = CharTokenizer.english()
    params, stats = _init_variables(cfg)

    def inferencer(cfg=cfg, **kw):
        return Inferencer(cfg, tok, params, stats, **kw)

    return types.SimpleNamespace(cfg=cfg, tok=tok, params=params,
                                 stats=stats, inferencer=inferencer)


@pytest.fixture(scope="session")
def tiny_streaming():
    """``ds2_streaming`` cut to two GRU-32 layers with a 4-frame
    lookahead: ``(cfg, tok, params, batch_stats)``."""
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.data import CharTokenizer

    cfg = get_config("ds2_streaming")
    cfg = dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, rnn_hidden=32, rnn_layers=2,
                                  conv_channels=(4, 4),
                                  lookahead_context=4, dtype="float32"),
        data=dataclasses.replace(cfg.data, max_label_len=32),
        features=dataclasses.replace(cfg.features, num_features=NF))
    return (cfg, CharTokenizer.english(), *_init_variables(cfg))


@pytest.fixture
def postmortems():
    """A private ``PostmortemWriter`` (own registry, so the process-wide
    counters stay out of it) whose JSONL collects in ``.buf``; hand
    ``.write`` to a controller as ``postmortem_fn`` and the writer
    itself to ``obs_lint``."""
    import io

    from deepspeech_tpu.obs.metrics import MetricsRegistry
    from deepspeech_tpu.resilience.postmortem import PostmortemWriter

    buf = io.StringIO()
    pm = PostmortemWriter(sink=buf, registry=MetricsRegistry())
    pm.buf = buf
    return pm


@pytest.fixture(scope="session")
def obs_lint():
    """``tools/check_obs_schema.scan`` over JSONL lines, over objects
    with ``emit_jsonl`` (telemetry) and over the ``postmortems``
    writer; returns the list of problems."""
    import io

    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    sys.path.insert(0, tools)
    try:
        import check_obs_schema
    finally:
        sys.path.remove(tools)

    def lint(*sources):
        lines = []
        for src in sources:
            if hasattr(src, "emit_jsonl"):
                buf = io.StringIO()
                src.emit_jsonl(buf)
                src = buf.getvalue()
            elif hasattr(src, "buf"):
                src = src.buf.getvalue()
            if isinstance(src, str):
                src = src.splitlines()
            lines.extend(ln for ln in src if ln.strip())
        assert lines, "nothing was emitted to lint"
        return check_obs_schema.scan(lines)

    return lint
