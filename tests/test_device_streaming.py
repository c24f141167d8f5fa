"""Streaming all-device engine (ops/device_streaming.py +
device_tokenize=True, stream_chunk_docs=N): raw byte windows through a
bounded on-device row accumulator.

Exactness contract is the all-device engine's: byte-identical to the
oracle whenever cleaned tokens fit the row width, WidthOverflow
fallback otherwise — independent of chunk size, accumulator growth
path, or window count."""

import numpy as np
import pytest

from conftest import read_letter_files

from parallel_computation_of_an_inverted_index_using_map_reduce_tpu import (
    IndexConfig,
    InvertedIndexModel,
    build_index,
    oracle_index,
    read_manifest,
)
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.manifest import (
    write_manifest,
)
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.synthetic import (
    write_corpus,
    zipf_corpus,
)
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.ops import (
    device_streaming as DS,
)
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.ops import (
    device_tokenizer as DT,
)


def _cfg(**kw):
    kw.setdefault("backend", "tpu")
    kw.setdefault("device_tokenize", True)
    kw.setdefault("stream_chunk_docs", 7)
    kw.setdefault("pad_multiple", 256)
    kw.setdefault("device_shards", 1)
    return IndexConfig(**kw)


def test_merge_count_is_exact_not_upper_bound():
    """The count _merge_unique_rows returns is the TRUE unique-row
    count: _row_first_mask masks all-INT32_MAX padding rows, so the
    first padding row is NOT counted as a first occurrence (advisor r3
    flagged the opposite; this pins the verified behavior — if the
    handle ever over-counts, _unique_bound loses its 'true count after
    resolution' meaning)."""
    def win(texts, first_id):
        buf = ("\x00".join(texts) + "\x00").encode()
        data = np.frombuffer(buf, np.uint8).copy()
        ends, pos = [], 0
        for t in texts:
            pos += len(t) + 1
            ends.append(pos)
        return (data, np.array(ends, np.int32),
                np.arange(first_id, first_id + len(texts), dtype=np.int32))

    windows = [["the cat sat", "a cat ran"], ["the dog sat", "cat cat cat"]]
    eng = DS.DeviceStreamEngine(width=12)
    for i, texts in enumerate(windows):
        data, ends, ids = win(texts, 1 + 2 * i)
        eng.feed(data, ends, ids,
                 tok_count=sum(len(t.split()) for t in texts),
                 max_len=max(len(w) for t in texts for w in t.split()))
    # both merge handles are still pending (depth-2 pipeline): resolve
    # them directly and compare against the ground-truth running counts
    truth, seen, doc = [], set(), 0
    for texts in windows:
        for t in texts:
            doc += 1
            seen.update((w, doc) for w in t.split())
        truth.append(len(seen))
    got = [int(np.asarray(h)) for h, _ in eng._pending]
    assert got == truth  # exact, not an upper bound
    counts = np.asarray(eng.finalize()["counts"])
    assert counts[1] == truth[-1]


def test_matches_goldens_smoke(smoke_fixture, tmp_path):
    m = read_manifest(smoke_fixture / "manifest.txt", base_dir=smoke_fixture)
    report = InvertedIndexModel(_cfg(stream_chunk_docs=2)).run(
        m, output_dir=tmp_path)
    assert report["stream_windows"] >= 2  # really streamed
    assert "sort_cols" in report          # really the DEVICE engine
    assert "stream_feed" in report["phases_ms"]
    assert read_letter_files(tmp_path) == read_letter_files(
        smoke_fixture / "golden")


@pytest.mark.parametrize("chunk", [1, 5, 1000])
def test_chunk_size_invariant_vs_oracle(tmp_path, chunk):
    docs = zipf_corpus(num_docs=33, vocab_size=700, tokens_per_doc=55, seed=5)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")
    build_index(m, _cfg(stream_chunk_docs=chunk),
                output_dir=tmp_path / "dev")
    assert read_letter_files(tmp_path / "dev") == read_letter_files(
        tmp_path / "oracle")


def test_matches_one_shot_engine(tmp_path):
    docs = zipf_corpus(num_docs=29, vocab_size=500, tokens_per_doc=48, seed=8)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    build_index(m, _cfg(stream_chunk_docs=None), output_dir=tmp_path / "one")
    build_index(m, _cfg(stream_chunk_docs=4), output_dir=tmp_path / "str")
    assert read_letter_files(tmp_path / "str") == read_letter_files(
        tmp_path / "one")


def test_accumulator_growth_path(tmp_path):
    """Tiny initial capacity forces the host-side doubling regrowth."""
    docs = zipf_corpus(num_docs=25, vocab_size=900, tokens_per_doc=70, seed=3)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")

    import parallel_computation_of_an_inverted_index_using_map_reduce_tpu.models.inverted_index as MI

    orig = DS.DeviceStreamEngine

    class Tiny(orig):
        def __init__(self, **kw):
            kw["initial_capacity"] = 256
            kw["window_pad"] = 256
            super().__init__(**kw)

    DS.DeviceStreamEngine = Tiny
    try:
        report = InvertedIndexModel(_cfg(stream_chunk_docs=3)).run(
            m, output_dir=tmp_path / "dev")
    finally:
        DS.DeviceStreamEngine = orig
    assert report["accumulator_capacity"] > 256  # growth really happened
    assert read_letter_files(tmp_path / "dev") == read_letter_files(
        tmp_path / "oracle")


def test_capacity_tracks_unique_rows_not_stream_length(tmp_path):
    """The bounded-memory claim: a long stream over a SMALL vocabulary
    must keep the accumulator at unique-pair scale (the host bound is
    tightened from the previous merge's true count), not grow with
    total fed tokens."""
    rng = np.random.default_rng(12)
    vocab = [("w%02d" % i).encode() for i in range(50)]
    docs = [b" ".join(rng.choice(vocab, 200)) for _ in range(40)]  # 8k tokens
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")

    orig = DS.DeviceStreamEngine

    class Tiny(orig):
        def __init__(self, **kw):
            kw["initial_capacity"] = 1024
            kw["window_pad"] = 256
            super().__init__(**kw)

    DS.DeviceStreamEngine = Tiny
    try:
        report = InvertedIndexModel(_cfg(stream_chunk_docs=2)).run(
            m, output_dir=tmp_path / "dev")
    finally:
        DS.DeviceStreamEngine = orig
    # unique pairs <= 50 words x 40 docs = 2000; a stream-length bound
    # would have doubled past total tokens (8192)
    assert report["accumulator_capacity"] <= 4096
    assert read_letter_files(tmp_path / "dev") == read_letter_files(
        tmp_path / "oracle")


def test_stream_checkpoint_kill_and_resume(tmp_path, monkeypatch):
    """VERDICT r3 #3: kill a checkpointed stream mid-run, resume, and
    get byte-identical output.  The injected crash reproduces the
    round-3 on-chip failure mode (TPU worker died ~9 min into the
    1M-doc stream, round 3) at a deterministic window."""
    docs = zipf_corpus(num_docs=40, vocab_size=120, tokens_per_doc=12, seed=9)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "golden")
    ckpt = tmp_path / "stream.ckpt.npz"

    cfg = _cfg(stream_chunk_docs=5, stream_checkpoint=str(ckpt),
               stream_checkpoint_every=2)
    # 40 docs / 5 per window = 8 windows; crash after window 5 leaves
    # the window-4 checkpoint as the resume point
    monkeypatch.setenv("MRI_TPU_STREAM_CRASH_AFTER_WINDOWS", "5")
    with pytest.raises(RuntimeError, match="injected stream crash"):
        InvertedIndexModel(cfg).run(m, output_dir=tmp_path / "out")
    assert ckpt.exists(), "crash left no checkpoint to resume from"

    monkeypatch.delenv("MRI_TPU_STREAM_CRASH_AFTER_WINDOWS")
    report = InvertedIndexModel(cfg).run(m, output_dir=tmp_path / "out")
    assert report["resumed_from_window"] == 4
    assert report["stream_windows"] == 8
    assert not ckpt.exists(), "completed run must clear its checkpoint"
    assert read_letter_files(tmp_path / "out") == read_letter_files(
        tmp_path / "golden")

    # uninterrupted checkpointed run: same output, checkpoint cleared
    report2 = InvertedIndexModel(cfg).run(m, output_dir=tmp_path / "out2")
    assert "resumed_from_window" not in report2
    assert read_letter_files(tmp_path / "out2") == read_letter_files(
        tmp_path / "golden")


@pytest.mark.parametrize("crash_at,every", [(2, 1), (3, 2), (7, 3)])
def test_stream_checkpoint_resume_any_crash_point(tmp_path, monkeypatch,
                                                  crash_at, every):
    """Property: crash at ANY window under ANY cadence, resume, output
    byte-identical — resume position must be exactly the last saved
    loop index regardless of alignment."""
    docs = zipf_corpus(num_docs=32, vocab_size=90, tokens_per_doc=9, seed=21)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "golden")
    ckpt = tmp_path / "s.npz"
    cfg = _cfg(stream_chunk_docs=4, stream_checkpoint=str(ckpt),
               stream_checkpoint_every=every)

    monkeypatch.setenv("MRI_TPU_STREAM_CRASH_AFTER_WINDOWS", str(crash_at))
    with pytest.raises(RuntimeError, match="injected stream crash"):
        InvertedIndexModel(cfg).run(m, output_dir=tmp_path / "out")
    monkeypatch.delenv("MRI_TPU_STREAM_CRASH_AFTER_WINDOWS")
    report = InvertedIndexModel(cfg).run(m, output_dir=tmp_path / "out")
    # last checkpointed window at or before the crash, on cadence (the
    # save at an aligned win_i runs BEFORE the crash hook fires)
    expected_resume = (crash_at // every) * every
    assert report["resumed_from_window"] == expected_resume
    assert report["stream_windows"] == 8
    assert read_letter_files(tmp_path / "out") == read_letter_files(
        tmp_path / "golden")


def test_stream_checkpoint_with_empty_windows(tmp_path, monkeypatch):
    """Windows that tokenize to nothing (digits/punctuation only) make
    the engine's windows_fed run BEHIND the loop index; the checkpoint
    stores the loop position, so resume must still land on the right
    window (the round-4 review's divergence scenario, now pinned)."""
    docs = [b"alpha beta", b"   \n  ", b" \t ",
            b"gamma delta", b"epsilon zeta", b"beta alpha",
            b"eta theta", b"iota kappa"]
    # chunk=1: 8 windows; windows 2 and 3 are whitespace-only — zero
    # TOKENS, so feed() returns before counting them (an all-digit doc
    # would still count: host_token_stats counts raw tokens)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "golden")
    ckpt = tmp_path / "s.npz"
    cfg = _cfg(stream_chunk_docs=1, stream_checkpoint=str(ckpt),
               stream_checkpoint_every=2)
    monkeypatch.setenv("MRI_TPU_STREAM_CRASH_AFTER_WINDOWS", "5")
    with pytest.raises(RuntimeError, match="injected stream crash"):
        InvertedIndexModel(cfg).run(m, output_dir=tmp_path / "out")
    monkeypatch.delenv("MRI_TPU_STREAM_CRASH_AFTER_WINDOWS")
    report = InvertedIndexModel(cfg).run(m, output_dir=tmp_path / "out")
    assert report["resumed_from_window"] == 4      # loop position
    assert report["stream_windows"] == 6           # non-empty windows
    assert read_letter_files(tmp_path / "out") == read_letter_files(
        tmp_path / "golden")


def test_stream_checkpoint_rejects_changed_config(tmp_path, monkeypatch):
    """A checkpoint written under one chunking must not silently feed a
    resume under another (window index would mean a different doc
    range — silent corruption)."""
    docs = zipf_corpus(num_docs=20, vocab_size=60, tokens_per_doc=10, seed=3)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    ckpt = tmp_path / "stream.ckpt.npz"

    monkeypatch.setenv("MRI_TPU_STREAM_CRASH_AFTER_WINDOWS", "3")
    with pytest.raises(RuntimeError, match="injected stream crash"):
        InvertedIndexModel(_cfg(
            stream_chunk_docs=4, stream_checkpoint=str(ckpt),
            stream_checkpoint_every=1)).run(m, output_dir=tmp_path / "out")
    monkeypatch.delenv("MRI_TPU_STREAM_CRASH_AFTER_WINDOWS")
    with pytest.raises(ValueError, match="different .* config|different manifest"):
        InvertedIndexModel(_cfg(
            stream_chunk_docs=6, stream_checkpoint=str(ckpt),
            stream_checkpoint_every=1)).run(m, output_dir=tmp_path / "out")


def test_stream_checkpoint_rejects_changed_synthetic_params(tmp_path,
                                                            monkeypatch):
    """Synthetic manifests have placeholder path labels, so the
    fingerprint must carry the generator parameters — resuming a
    seed-11 stream under seed-12 data would silently mix corpora."""
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.synthetic import (
        synthetic_manifest,
    )

    ckpt = tmp_path / "stream.ckpt.npz"
    monkeypatch.setenv("MRI_TPU_STREAM_CRASH_AFTER_WINDOWS", "2")
    m1 = synthetic_manifest(num_docs=30, vocab_size=50, tokens_per_doc=8,
                            seed=11)
    with pytest.raises(RuntimeError, match="injected stream crash"):
        InvertedIndexModel(_cfg(
            stream_chunk_docs=10, stream_checkpoint=str(ckpt),
            stream_checkpoint_every=1)).run(m1, output_dir=tmp_path / "out")
    monkeypatch.delenv("MRI_TPU_STREAM_CRASH_AFTER_WINDOWS")
    m2 = synthetic_manifest(num_docs=30, vocab_size=50, tokens_per_doc=8,
                            seed=12)
    with pytest.raises(ValueError, match="different manifest"):
        InvertedIndexModel(_cfg(
            stream_chunk_docs=10, stream_checkpoint=str(ckpt),
            stream_checkpoint_every=1)).run(m2, output_dir=tmp_path / "out")


def _fed_engine():
    """A DeviceStreamEngine with one window folded, for snapshot tests."""
    texts = ["the cat sat", "a cat ran here"]
    buf = ("\x00".join(texts) + "\x00").encode()
    data = np.frombuffer(buf, np.uint8).copy()
    ends, pos = [], 0
    for t in texts:
        pos += len(t) + 1
        ends.append(pos)
    eng = DS.DeviceStreamEngine(width=12)
    eng.feed(data, np.array(ends, np.int32),
             np.arange(1, len(texts) + 1, dtype=np.int32),
             tok_count=sum(len(t.split()) for t in texts),
             max_len=max(len(w) for t in texts for w in t.split()))
    return eng


def test_checkpoint_budget_stretches_cadence(tmp_path, monkeypatch):
    """VERDICT r4 weak #3: a cadence save whose projected fetch time
    exceeds MRI_TPU_CKPT_BUDGET_S is skipped (recorded, not paid) — but
    only MRI_TPU_CKPT_STRETCH times in a row, then one save is FORCED
    so a mis-calibrated rate can never lock checkpointing out entirely
    (review r5).  The stream still completes byte-identically and
    per-save timings are listed when saves happen."""
    docs = zipf_corpus(num_docs=24, vocab_size=80, tokens_per_doc=10, seed=6)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "golden")
    ckpt = tmp_path / "s.npz"
    cfg = _cfg(stream_chunk_docs=4, stream_checkpoint=str(ckpt),
               stream_checkpoint_every=1)

    # zero budget, default stretch=4: cadence points are windows 1-5
    # (6 is last) -> 4 skips then a forced save at window 5
    monkeypatch.setenv("MRI_TPU_CKPT_BUDGET_S", "0")
    report = InvertedIndexModel(cfg).run(m, output_dir=tmp_path / "out")
    assert report["checkpoint_skips"] == 4
    assert len(report["checkpoint_skipped_projection_s"]) == 4
    assert report["checkpoint_saves"] == 1   # the forced save
    assert not ckpt.exists()                 # completed run clears it
    assert read_letter_files(tmp_path / "out") == read_letter_files(
        tmp_path / "golden")

    # stretch=0: the budget can delay nothing, every cadence point saves
    monkeypatch.setenv("MRI_TPU_CKPT_STRETCH", "0")
    report0 = InvertedIndexModel(cfg).run(m, output_dir=tmp_path / "out0")
    assert report0["checkpoint_saves"] == 5
    assert "checkpoint_skips" not in report0
    monkeypatch.delenv("MRI_TPU_CKPT_STRETCH")

    # generous budget: saves happen and each one's wall time is listed
    monkeypatch.setenv("MRI_TPU_CKPT_BUDGET_S", "3600")
    report2 = InvertedIndexModel(cfg).run(m, output_dir=tmp_path / "out2")
    assert report2["checkpoint_saves"] == 5
    assert len(report2["checkpoint_ms_per_save"]) == 5
    assert "checkpoint_skips" not in report2
    assert read_letter_files(tmp_path / "out2") == read_letter_files(
        tmp_path / "golden")


def test_rows_curve_tracks_resolved_merge_counts(tmp_path):
    """unique_rows_curve is the resolved per-merge accumulator count,
    monotone nondecreasing, ending at the true unique-pair total."""
    docs = zipf_corpus(num_docs=20, vocab_size=60, tokens_per_doc=8, seed=4)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    report = InvertedIndexModel(_cfg(stream_chunk_docs=4)).run(
        m, output_dir=tmp_path / "out")
    curve = report["unique_rows_curve"]
    # 5 windows, 2-deep pipeline: at least 3 counts resolve in feed
    assert len(curve) >= 3
    assert curve == sorted(curve)
    assert curve[-1] <= report["unique_pairs"]


def test_rows_curve_survives_crash_resume(tmp_path, monkeypatch):
    """A resumed run's curve must cover the WHOLE stream: the pre-crash
    history rides the checkpoint (review r5 — without it the scale
    artifact's growth curve starts mid-stream on exactly the long runs
    it exists to observe)."""
    docs = zipf_corpus(num_docs=32, vocab_size=90, tokens_per_doc=9, seed=2)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    ckpt = tmp_path / "s.npz"
    cfg = _cfg(stream_chunk_docs=4, stream_checkpoint=str(ckpt),
               stream_checkpoint_every=2)
    monkeypatch.setenv("MRI_TPU_STREAM_CRASH_AFTER_WINDOWS", "5")
    with pytest.raises(RuntimeError, match="injected stream crash"):
        InvertedIndexModel(cfg).run(m, output_dir=tmp_path / "out")
    monkeypatch.delenv("MRI_TPU_STREAM_CRASH_AFTER_WINDOWS")
    resumed = InvertedIndexModel(cfg).run(m, output_dir=tmp_path / "out")

    whole = InvertedIndexModel(_cfg(stream_chunk_docs=4)).run(
        m, output_dir=tmp_path / "out2")
    rc, wc = resumed["unique_rows_curve"], whole["unique_rows_curve"]
    # the checkpoint (window 4) drained every in-flight merge, so the
    # resumed curve's prefix is the uninterrupted run's first 4 counts
    assert rc[:4] == wc[:4]
    assert rc == sorted(rc) and len(rc) >= len(wc)
    assert rc[-1] <= resumed["unique_pairs"]


def test_snapshot_prefix_fetch_matches_full_fetch():
    """The granule-padded prefix fetch (snapshot cost trim) must hand
    back exactly the rows the full-capacity fetch would: every valid
    row lives in acc[:count], so a pad >= count loses nothing."""
    eng = _fed_engine()
    eng._snapshot_granule = 8   # force pad < cap (cap is 1 << 16)
    assert eng.snapshot_nbytes < (2 * eng._num_groups + 1) * eng._cap * 4
    trimmed = eng.snapshot()

    full = _fed_engine()
    full._snapshot_granule = full._cap   # pad == cap -> full device_get
    reference = full.snapshot()

    assert trimmed["count"] == reference["count"] > 0
    for a, b in zip(trimmed["columns"], reference["columns"]):
        np.testing.assert_array_equal(a, b)
    # and the trimmed snapshot still restores into a working engine
    eng2 = DS.DeviceStreamEngine(width=12)
    eng2.restore(trimmed)
    assert eng2.windows_fed == trimmed["windows_fed"]


def test_restore_rejects_truncated_checkpoint():
    """A truncated/corrupt snapshot must fail with the same clear
    ValueError diagnostics as the width/column-count checks, not an
    opaque numpy broadcast error (advisor r4)."""
    snap = _fed_engine().snapshot()

    over = dict(snap, count=snap["cap"] + 1)
    with pytest.raises(ValueError, match="exceeds its capacity"):
        DS.DeviceStreamEngine(width=12).restore(over)

    cut = dict(snap, columns=[c[:-1] for c in snap["columns"]])
    with pytest.raises(ValueError, match="truncated or corrupt"):
        DS.DeviceStreamEngine(width=12).restore(cut)

    one_short = dict(snap, columns=(snap["columns"][:-1]
                                    + [snap["columns"][-1][:-1]]))
    with pytest.raises(ValueError, match="column .* truncated or corrupt"):
        DS.DeviceStreamEngine(width=12).restore(one_short)

    # the untouched snapshot still restores (validation is not lossy)
    fresh = DS.DeviceStreamEngine(width=12)
    fresh.restore(snap)
    assert fresh.windows_fed == snap["windows_fed"]


def test_width_overflow_clears_stream_checkpoint(tmp_path):
    """A WidthOverflow fallback abandons the stream for the host path;
    the checkpoint must not survive to poison later runs."""
    docs = [b"short words here", b"also small ones",
            b"x" * 60 + b" overflowing token window"]
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "golden")
    ckpt = tmp_path / "stream.ckpt.npz"
    report = InvertedIndexModel(_cfg(
        stream_chunk_docs=1, device_tokenize_width=48,
        stream_checkpoint=str(ckpt), stream_checkpoint_every=1)).run(
            m, output_dir=tmp_path / "out")
    assert "device_tokenize_fallback" in report
    assert not ckpt.exists(), "fallback left a stale stream checkpoint"
    assert read_letter_files(tmp_path / "out") == read_letter_files(
        tmp_path / "golden")


def test_stream_checkpoint_config_validation():
    with pytest.raises(ValueError, match="single-chip only"):
        IndexConfig(backend="tpu", device_tokenize=True,
                    stream_chunk_docs=4, stream_checkpoint="x.npz")
    with pytest.raises(ValueError, match="streaming all-device engine"):
        IndexConfig(backend="tpu", stream_checkpoint="x.npz")
    with pytest.raises(ValueError, match="stream_checkpoint_every"):
        IndexConfig(stream_checkpoint_every=0)


def test_width_overflow_falls_back_exactly(tmp_path):
    """An over-width token in a LATER window must abort the whole run
    to the host path with byte-identical output."""
    docs = [b"early window words"] * 6 + [b"a" * 30 + b" tail"] + [b"end"]
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")
    report = InvertedIndexModel(
        _cfg(stream_chunk_docs=3, device_tokenize_width=16)).run(
        m, output_dir=tmp_path / "dev")
    assert "device_tokenize_fallback" in report
    assert read_letter_files(tmp_path / "dev") == read_letter_files(
        tmp_path / "oracle")


def test_empty_and_numbers_only_corpus(tmp_path):
    docs = [b"", b"   ", b"123 456", b"--- !!!"]
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    InvertedIndexModel(_cfg(stream_chunk_docs=2)).run(
        m, output_dir=tmp_path / "dev")
    assert read_letter_files(tmp_path / "dev") == b""


# -- mesh variant (parallel/dist_device_streaming.py) ---------------------


def _dist_cfg(**kw):
    kw.setdefault("device_shards", None)  # all 8 virtual devices
    return _cfg(**kw)


def _needs_mesh():
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("mesh streaming device engine needs >= 2 devices")


@pytest.mark.parametrize("seed,chunk", [(3, 4), (14, 11)])
def test_dist_stream_vs_oracle(tmp_path, seed, chunk):
    _needs_mesh()
    docs = zipf_corpus(num_docs=35, vocab_size=650, tokens_per_doc=50,
                       seed=seed)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")
    report = InvertedIndexModel(_dist_cfg(stream_chunk_docs=chunk)).run(
        m, output_dir=tmp_path / "dev")
    assert report["device_shards"] > 1
    assert report["stream_windows"] >= 2
    assert read_letter_files(tmp_path / "dev") == read_letter_files(
        tmp_path / "oracle")


def test_dist_stream_matches_single_chip_stream(tmp_path):
    _needs_mesh()
    docs = zipf_corpus(num_docs=27, vocab_size=400, tokens_per_doc=45,
                       seed=22)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    build_index(m, _cfg(stream_chunk_docs=5), output_dir=tmp_path / "one")
    build_index(m, _dist_cfg(stream_chunk_docs=5),
                output_dir=tmp_path / "mesh")
    assert read_letter_files(tmp_path / "mesh") == read_letter_files(
        tmp_path / "one")


def test_dist_stream_growth_and_retry_path(tmp_path):
    """Tiny per-owner capacity forces the merge-retry + regrow path;
    output must stay byte-identical."""
    _needs_mesh()
    docs = zipf_corpus(num_docs=30, vocab_size=800, tokens_per_doc=60,
                       seed=9)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")

    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.parallel import (
        dist_device_streaming as DDS,
    )

    orig = DDS.DistDeviceStreamEngine

    class Tiny(orig):
        def __init__(self, **kw):
            kw["initial_capacity"] = 128
            kw["window_pad"] = 128
            super().__init__(**kw)

    DDS.DistDeviceStreamEngine = Tiny
    try:
        report = InvertedIndexModel(_dist_cfg(stream_chunk_docs=6)).run(
            m, output_dir=tmp_path / "dev")
    finally:
        DDS.DistDeviceStreamEngine = orig
    assert report["accumulator_capacity_per_owner"] > 128
    assert read_letter_files(tmp_path / "dev") == read_letter_files(
        tmp_path / "oracle")


def test_dist_stream_fewer_docs_than_chips(tmp_path):
    """Chunks smaller than the mesh leave empty byte shards — they
    must contribute nothing, not crash."""
    _needs_mesh()
    docs = [b"alpha beta", b"beta gamma", b"delta"]
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")
    build_index(m, _dist_cfg(stream_chunk_docs=2),
                output_dir=tmp_path / "dev")
    assert read_letter_files(tmp_path / "dev") == read_letter_files(
        tmp_path / "oracle")


def test_dist_stream_width_overflow_falls_back(tmp_path):
    _needs_mesh()
    docs = [b"short words"] * 4 + [b"b" * 30 + b" tail"]
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")
    report = InvertedIndexModel(
        _dist_cfg(stream_chunk_docs=2, device_tokenize_width=16)).run(
        m, output_dir=tmp_path / "dev")
    assert "device_tokenize_fallback" in report
    assert read_letter_files(tmp_path / "dev") == read_letter_files(
        tmp_path / "oracle")


def test_pack_unpack_groups_roundtrip():
    """unpack_groups must be the exact inverse of pack_groups on valid
    rows for every column count."""
    rng = np.random.default_rng(0)
    ncols = 12
    n = 64
    # random cleaned rows: 0-terminated lowercase prefixes
    rows = np.zeros((n, 4 * ncols), np.uint8)
    for i in range(n):
        ln = int(rng.integers(1, 4 * ncols + 1))
        rows[i, :ln] = rng.integers(97, 123, ln, np.uint8)
    r32 = rows.reshape(n, ncols, 4).astype(np.int64)
    cols = tuple(
        ((r32[:, c, 0] << 24) | (r32[:, c, 1] << 16)
         | (r32[:, c, 2] << 8) | r32[:, c, 3]).astype(np.int32)
        for c in range(ncols))
    import jax.numpy as jnp

    jcols = tuple(jnp.asarray(c) for c in cols)
    groups = DT.pack_groups(jcols, ncols)
    back = DT.unpack_groups(groups, ncols)
    for want, got in zip(cols, back):
        np.testing.assert_array_equal(want, np.asarray(got))
