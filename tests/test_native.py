"""Tests for the native C++ host runtime (native/src, SURVEY.md §2
bolded components: C++ beam-search decoder, n-gram LM engine, native
data loader/featurizer).

Strategy (SURVEY.md §4): every native component is diffed against its
tested pure-Python oracle — NGramLM, prefix_beam_search_host,
featurize_np/load_audio — on randomized and edge-case inputs.
"""

import os
import tempfile
import wave

import numpy as np
import pytest

from deepspeech_tpu.config import get_config
from deepspeech_tpu.data.features import featurize_np, load_audio
from deepspeech_tpu.decode.beam_host import prefix_beam_search_host
from deepspeech_tpu.decode.ngram import NGramLM
from deepspeech_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(),
    reason=f"ds2native unavailable: {native.build_error()}")

# Word-level LM over a char vocab: blank=0, space=1, a..e = 2..6.
ARPA = """\
\\data\\
ngram 1=7
ngram 2=4

\\1-grams:
-0.5\t<s>\t-0.30103
-0.9\t</s>
-0.6\tab\t-0.30103
-0.7\tba\t-0.30103
-0.8\tcab\t-0.2
-1.0\tace\t-0.1
-1.2\t<unk>

\\2-grams:
-0.2\t<s> ab
-0.3\tab ba
-0.4\tba </s>
-0.25\tab cab
\\end\\
"""

CHARS = {1: " ", 2: "a", 3: "b", 4: "c", 5: "d", 6: "e"}


def id_to_char(i):
    return CHARS.get(int(i), "?")


@pytest.fixture(scope="module")
def arpa_path(tmp_path_factory):
    p = tmp_path_factory.mktemp("lm") / "tiny.arpa"
    p.write_text(ARPA)
    return str(p)


@pytest.fixture(scope="module")
def lms(arpa_path):
    return NGramLM.from_arpa(arpa_path), native.NativeNGram(arpa_path)


def random_log_probs(rng, t, v, scale=1.5):
    logits = rng.normal(size=(t, v)).astype(np.float32) * scale
    return logits - np.log(np.exp(logits).sum(-1, keepdims=True))


# ---------------------------------------------------------------------------
# n-gram LM engine
# ---------------------------------------------------------------------------

def test_lm_matches_python_oracle(lms):
    py, cc = lms
    assert cc.order == py.order
    sentences = ["ab ba", "ba ab", "ab cab ace", "zebra ab", "", "ab ab ab"]
    for s in sentences:
        assert cc.score_sentence(s) == pytest.approx(py.score_sentence(s),
                                                     abs=1e-6)
        assert cc.score_sentence(s, include_eos=False) == pytest.approx(
            py.score_sentence(s, include_eos=False), abs=1e-6)


def test_lm_score_word_backoff_unk_eos(lms):
    py, cc = lms
    cases = [
        ([], "ab", False),          # direct <s> bigram
        (["ab"], "ba", False),      # direct bigram
        (["ba"], "ab", False),      # backoff path
        (["ab"], "zebra", False),   # OOV word -> <unk>
        (["zebra"], "ab", False),   # OOV history
        (["ab"], "ba", True),       # eos transition
        (["ab", "", "ba"], "cab", False),  # empty history words filtered
    ]
    for hist, w, eos in cases:
        assert cc.score_word(hist, w, eos) == pytest.approx(
            py.score_word(hist, w, eos), abs=1e-6), (hist, w, eos)


def test_lm_load_failure_raises(tmp_path):
    bad = tmp_path / "empty.arpa"
    bad.write_text("no data here\n")
    with pytest.raises(ValueError):
        native.NativeNGram(str(bad))


# ---------------------------------------------------------------------------
# beam search decoder
# ---------------------------------------------------------------------------

def test_beam_matches_oracle_no_lm():
    rng = np.random.default_rng(0)
    for trial in range(8):
        t, v = int(rng.integers(4, 25)), int(rng.integers(3, 9))
        lp = random_log_probs(rng, t, v)
        py = prefix_beam_search_host(lp, beam_width=8)
        cc = native.beam_search_native(lp, beam_width=8)
        for (p1, s1), (p2, s2) in zip(py[:5], cc[:5]):
            assert p1 == p2, (trial, p1, p2)
            assert s1 == pytest.approx(s2, abs=1e-4)


def test_beam_matches_oracle_with_pruning():
    rng = np.random.default_rng(1)
    lp = random_log_probs(rng, 20, 8)
    kw = dict(beam_width=6, prune_log_prob=np.log(1e-2))
    py = prefix_beam_search_host(lp, **kw)
    cc = native.beam_search_native(lp, **kw)
    assert [p for p, _ in cc[:4]] == [p for p, _ in py[:4]]


@pytest.mark.parametrize("mode", ["word", "char"])
def test_beam_matches_oracle_with_lm_fusion(lms, mode):
    py_lm, c_lm = lms
    space = 1 if mode == "word" else None
    rng = np.random.default_rng(2 if mode == "word" else 3)
    for trial in range(6):
        lp = random_log_probs(rng, 15, 7)
        kw = dict(beam_width=8, lm_alpha=1.3, lm_beta=0.4, space_id=space,
                  id_to_char=id_to_char)
        py = prefix_beam_search_host(lp, lm=py_lm, **kw)
        cc = native.beam_search_native(lp, lm=c_lm, **kw)
        for (p1, s1), (p2, s2) in zip(py, cc):
            assert p1 == p2, (trial, mode, p1, p2)
            assert s1 == pytest.approx(s2, abs=1e-4)


def test_beam_edge_cases():
    # T=0 -> single empty hypothesis with score 0.
    lp = np.zeros((0, 4), np.float32)
    out = native.beam_search_native(lp, beam_width=4)
    assert out[0][0] == () and out[0][1] == pytest.approx(0.0)
    # All-blank frames -> empty prefix wins.
    lp = np.log(np.full((5, 4), 1e-3, np.float32))
    lp[:, 0] = np.log(0.997)
    out = native.beam_search_native(lp, beam_width=4)
    assert out[0][0] == ()


def test_beam_batch_threaded_matches_single(lms):
    py_lm, c_lm = lms
    rng = np.random.default_rng(4)
    b, t, v = 5, 12, 7
    lp = np.stack([random_log_probs(rng, t, v) for _ in range(b)])
    lens = np.array([t, 9, t, 5, 2], np.int32)
    res = native.beam_search_batch_native(
        lp, lens, beam_width=8, lm=c_lm, lm_alpha=1.0, lm_beta=0.2,
        space_id=1, id_to_char=id_to_char, nbest=3, n_threads=3)
    assert len(res) == b
    for i in range(b):
        py = prefix_beam_search_host(
            lp[i][:lens[i]], beam_width=8, lm=py_lm, lm_alpha=1.0,
            lm_beta=0.2, space_id=1, id_to_char=id_to_char)
        for (p1, s1), (p2, s2) in zip(py[:3], res[i]):
            assert p1 == p2
            assert s1 == pytest.approx(s2, abs=1e-4)


def test_beam_invalid_args():
    lp = np.zeros((3, 4), np.float32)
    with pytest.raises(RuntimeError):
        native.beam_search_native(lp, beam_width=0)


# ---------------------------------------------------------------------------
# featurizer + wav loader
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fcfg():
    return get_config("dev_slice").features


def test_featurize_matches_numpy_oracle(fcfg):
    rng = np.random.default_rng(0)
    for n in [319, 320, 1000, 16000, 48001]:
        audio = rng.normal(size=(n,)).astype(np.float32) * 0.3
        ref = featurize_np(audio, fcfg)
        nat = native.featurize_native(audio, fcfg)
        assert nat.shape == ref.shape
        if ref.size:
            assert np.abs(ref - nat).max() < 2e-3


def _write_wav(path, audio, rate=16000, width=2):
    nch = audio.shape[1] if audio.ndim > 1 else 1
    with wave.open(path, "wb") as w:
        w.setnchannels(nch)
        w.setsampwidth(width)
        w.setframerate(rate)
        if width == 2:
            w.writeframes((audio * 32767).astype(np.int16).tobytes())
        else:
            w.writeframes(((audio * 127) + 128).astype(np.uint8).tobytes())


def test_load_wav_matches_python(fcfg, tmp_path):
    rng = np.random.default_rng(1)
    for i, (nch, width) in enumerate([(1, 2), (2, 2), (1, 1)]):
        audio = (rng.normal(size=(8000 + i * 777, nch)) * 0.2).clip(-1, 1)
        p = str(tmp_path / f"t{i}.wav")
        _write_wav(p, audio, width=width)
        ref = load_audio(p, 16000)
        nat = native.load_wav_native(p, 16000)
        assert ref.shape == nat.shape
        assert np.abs(ref - nat).max() < 1e-4


def test_load_wav_wrong_rate_raises(tmp_path):
    p = str(tmp_path / "r8k.wav")
    _write_wav(p, np.zeros((800,), np.float32), rate=8000)
    with pytest.raises(ValueError):
        native.load_wav_native(p, 16000)


def test_load_featurize_batch_end_to_end(fcfg, tmp_path):
    rng = np.random.default_rng(2)
    paths = []
    for i in range(3):
        audio = (rng.normal(size=(12000 + 3000 * i,)) * 0.2).clip(-1, 1)
        p = str(tmp_path / f"b{i}.wav")
        _write_wav(p, audio)
        paths.append(p)
    paths.append(str(tmp_path / "missing.wav"))  # must not kill the batch
    feats, frames = native.load_featurize_batch(paths, fcfg, max_frames=120,
                                                n_threads=2)
    assert feats.shape == (4, 120, fcfg.num_features)
    assert frames[3] == -1
    for i in range(3):
        ref = featurize_np(load_audio(paths[i], 16000), fcfg)
        t = min(ref.shape[0], 120)
        assert frames[i] == t
        assert np.abs(feats[i, :t] - ref[:t]).max() < 2e-3
        assert np.all(feats[i, t:] == 0)


def test_native_pipeline_matches_python_pipeline(tmp_path, monkeypatch):
    """The C++ loader path of DataPipeline produces the same batches as
    the numpy path (features to 2e-3; lens/labels exactly)."""
    import dataclasses

    from deepspeech_tpu.data import CharTokenizer, DataPipeline
    from deepspeech_tpu.data.manifest import Utterance

    rng = np.random.default_rng(5)
    utts = []
    for i in range(6):
        n = 8000 + 1500 * i
        audio = (rng.normal(size=(n,)) * 0.2).clip(-1, 1)
        p = str(tmp_path / f"u{i}.wav")
        _write_wav(p, audio)
        utts.append(Utterance(p, "hello world"[: 5 + i], n / 16000.0))

    cfg = get_config("dev_slice")
    cfg = dataclasses.replace(
        cfg, data=dataclasses.replace(cfg.data, batch_size=3,
                                      bucket_frames=(60, 120)))
    tok = CharTokenizer.english()
    # Force the native path by making the cache threshold 0 utterances.
    monkeypatch.setattr(DataPipeline, "MAX_CACHED_UTTS", 0)
    pipe_native = DataPipeline(cfg, tok, utterances=utts)
    assert pipe_native._native
    cfg_py = dataclasses.replace(
        cfg, data=dataclasses.replace(cfg.data, native_loader=False))
    pipe_py = DataPipeline(cfg_py, tok, utterances=utts)
    assert not pipe_py._native

    for (bn, nb), (bp, _) in zip(pipe_native.eval_epoch(),
                                 pipe_py.eval_epoch()):
        assert np.array_equal(bn["feat_lens"], bp["feat_lens"])
        assert np.array_equal(bn["labels"], bp["labels"])
        assert np.array_equal(bn["label_lens"], bp["label_lens"])
        assert np.abs(bn["features"] - bp["features"]).max() < 2e-3


def test_infer_beam_fused_native_matches_python(lms, arpa_path):
    """Inferencer beam_fused via the C++ decoder == Python oracle."""
    import dataclasses

    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.infer import Inferencer
    from deepspeech_tpu.models import create_model
    import jax

    tok = CharTokenizer.english()
    cfg = get_config("dev_slice")
    cfg = dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, rnn_hidden=32, rnn_layers=1,
                                  conv_channels=(2, 2), dtype="float32",
                                  vocab_size=tok.vocab_size),
        decode=dataclasses.replace(cfg.decode, mode="beam_fused",
                                   beam_width=8, lm_path=arpa_path,
                                   lm_alpha=0.6, lm_beta=0.2),
    )
    model = create_model(cfg.model)
    rng = np.random.default_rng(0)
    feats = np.asarray(rng.normal(size=(2, 40, cfg.features.num_features)),
                       np.float32)
    lens = np.asarray([40, 24], np.int32)
    variables = model.init(jax.random.PRNGKey(0), feats, lens, train=False)

    def run(host_impl):
        c = dataclasses.replace(
            cfg, decode=dataclasses.replace(cfg.decode,
                                            host_impl=host_impl))
        inf = Inferencer(c, tok, params=variables["params"],
                         batch_stats=variables.get("batch_stats", {}))
        batch = {"features": feats, "feat_lens": lens}
        return inf.decode_batch(batch)

    assert run("native") == run("python")


def test_featurize_batch_in_memory(fcfg):
    rng = np.random.default_rng(3)
    audios = [rng.normal(size=(n,)).astype(np.float32)
              for n in (5000, 16000, 200)]  # 200 < one window -> 0 frames
    feats, frames = native.featurize_batch_native(audios, fcfg,
                                                  max_frames=60)
    assert frames[2] == 0
    for i in range(2):
        ref = featurize_np(audios[i], fcfg)
        t = min(ref.shape[0], 60)
        assert frames[i] == t
        assert np.abs(feats[i, :t] - ref[:t]).max() < 2e-3


def test_stale_source_hash_rebuilds_despite_newer_mtime(tmp_path,
                                                        monkeypatch):
    """Staleness is decided by the CONTENT of native/src (a hash
    recorded beside the binary), not by mtimes: a copied tree has
    arbitrary mtimes, and the library loaded must be the one the
    committed sources build."""
    from deepspeech_tpu.native import build

    src, out = tmp_path / "src", tmp_path / "build"
    src.mkdir()
    out.mkdir()
    (src / "a.cc").write_text("int one() { return 1; }\n")
    (src / "a.h").write_text("int one();\n")
    lib = out / "libds2native.so"
    monkeypatch.setattr(build, "_SRC_DIR", str(src))
    monkeypatch.setattr(build, "_BUILD_DIR", str(out))
    monkeypatch.setattr(build, "_LIB_PATH", str(lib))
    monkeypatch.setattr(build, "_HASH_PATH", str(lib) + ".srchash")
    compiles = []

    def fake_gxx(cmd, **kw):
        compiles.append(cmd)
        with open(cmd[cmd.index("-o") + 1], "w") as f:
            f.write(f"binary {len(compiles)}")
        return build.subprocess.CompletedProcess(cmd, 0, "", "")

    monkeypatch.setattr(build.subprocess, "run", fake_gxx)
    assert build._needs_build()  # nothing there yet
    build._build()
    assert lib.read_text() == "binary 1" and not build._needs_build()

    # Sources touched but unchanged: newer than the binary, not stale.
    later = os.path.getmtime(lib) + 100
    os.utime(src / "a.cc", (later, later))
    assert not build._needs_build()

    # Sources changed, binary made to look newer: stale all the same.
    (src / "a.h").write_text("int one(); int two();\n")
    os.utime(lib, (later + 100, later + 100))
    assert build._needs_build()
    build._build()
    assert lib.read_text() == "binary 2" and not build._needs_build()
    assert len(compiles) == 2
