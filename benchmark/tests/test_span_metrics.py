"""The readers of the program's spans and counters, on fabricated
``before``/``after`` snapshots and build reports: each reads its number,
and reads None where the program does not report the span or counter."""

from pathlib import Path

import pytest

from benchmark import run as bench_run

SERVE = ("serve.device_wait_ms", "serve.rescore_ms", "serve.reply_ms",
         "serve.dispatcher_busy_share", "serve.memo_miss_share")


def _run(**data) -> bench_run.Run:
    run = bench_run.Run(cell={}, config={}, traffic={}, seed=0, seconds=1,
                        work=Path("."))
    run.data.update(data)
    return run


def _snap(*, ops, device_ms, rescore_ms, reply, batch_ms, uptime_s,
          hits, misses) -> dict:
    """A serve snapshot shaped like ``drivers/serve.py``'s."""
    return {"queue_wait": {}, "stats": {
        "engine": {
            "ops": {op: {"calls": n, "total_ms": 10.0 * n}
                    for op, n in ops.items()},
            "steps": {"device": {"calls": 9, "total_ms": device_ms},
                      "rescore": {"calls": 3, "total_ms": rescore_ms}},
            "bm25_memo": {"hits": hits, "misses": misses},
        },
        "steps": {"reply": {"calls": reply[0], "total_ms": reply[1]},
                  "batch": {"calls": 5, "total_ms": batch_ms}},
        "uptime_s": uptime_s,
    }}


BEFORE = _snap(ops={"df": 4, "top_k_scored": 6}, device_ms=100.0,
               rescore_ms=30.0, reply=(10, 5.0), batch_ms=400.0,
               uptime_s=100.0, hits=70, misses=30)
AFTER = _snap(ops={"df": 10, "top_k_scored": 16, "and": 4},
              device_ms=340.0, rescore_ms=80.0, reply=(30, 15.0),
              batch_ms=2400.0, uptime_s=110.0, hits=160, misses=40)

WANT = {
    # 240 ms over 6 + 10 + 4 engine calls
    "serve.device_wait_ms": 12.0,
    # 50 ms over 10 ranked calls
    "serve.rescore_ms": 5.0,
    # 10 ms over 20 replies
    "serve.reply_ms": 0.5,
    # 2,000 ms busy over 10 s
    "serve.dispatcher_busy_share": 20.0,
    # 10 misses of 100 probes
    "serve.memo_miss_share": 10.0,
}


@pytest.mark.parametrize("name", SERVE)
def test_serve_reader(name):
    got = bench_run.read_metric(name, _run(before=BEFORE, after=AFTER))
    assert got == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", SERVE)
def test_serve_reader_without_the_span(name):
    """A program without the span or counter (the parent of the change
    that added it), or a run with no snapshots, reads None."""
    def bare(snap):
        st = {k: v for k, v in snap["stats"].items()
              if k not in ("steps", "uptime_s")}
        st["engine"] = {k: v for k, v in st["engine"].items()
                        if k not in ("steps", "bm25_memo")}
        return {**snap, "stats": st}

    assert bench_run.read_metric(
        name, _run(before=bare(BEFORE), after=bare(AFTER))) is None
    assert bench_run.read_metric(name, _run()) is None


def test_rescore_counts_a_step_first_seen_in_the_window():
    before = {**BEFORE, "stats": {**BEFORE["stats"], "engine": {
        **BEFORE["stats"]["engine"],
        "steps": {"device": {"calls": 9, "total_ms": 100.0}}}}}
    got = bench_run.read_metric("serve.rescore_ms",
                                _run(before=before, after=AFTER))
    assert got == pytest.approx(8.0)  # 80 ms over 10 ranked calls


def test_build_pack_reader():
    reports = [{"phases_ms": {"emit": 900.0}, "artifact_build_ms": 600.0},
               {"phases_ms": {"emit": 1000.0}, "artifact_build_ms": 700.0}]
    assert bench_run.read_metric("build.pack_ms", _run(reports=reports)) \
        == pytest.approx(650.0)
    assert bench_run.read_metric(
        "build.pack_ms", _run(reports=[{"phases_ms": {"emit": 1.0}}])) \
        is None
    assert bench_run.read_metric("build.pack_ms", _run()) is None
