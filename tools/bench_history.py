#!/usr/bin/env python
"""Aggregate the checked-in BENCH_*.json files into one trajectory table.

Every optimization round leaves a ``BENCH_<NAME>_rNN.json`` at the repo
root; individually they answer "how fast was round NN", but nobody can
see the arc without opening a dozen schemas.  This tool flattens them
into one table — file, headline metric, value, and the ratio to **that
round's own baseline**.

The ratio column deliberately never compares against a fixed global
number: the r13 scrape re-pricing showed that a ratio quoted against
another round's gate silently rots as the gate moves (r10's 0.02% was
priced against the 32K r09 gate, r13's 0.03% against the 60K r11
gate — comparable only because each was priced in-run against its own
round).  Each row's basis therefore names the same-run or same-round
baseline it was measured against.

Modes (default prints the table to stdout):
  --check   exit 1 when the README "Bench trajectory" block between
            the benchhistory markers drifts from the generated table
            (wired into `make lint`)
  --write   regenerate the README block in place
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]

BEGIN = "<!-- benchhistory:begin -->"
END = "<!-- benchhistory:end -->"

_ROUND_RE = re.compile(r"_r(\d+)\.json$")
#: files with no _rNN suffix but a known round
_ROUND_OVERRIDES: dict[str, int] = {}

#: per-file "ratio to the round's own baseline" text, where the file's
#: schema carries one (lambda: full doc dict, headline line dict)
_BASIS = {
    "BENCH_SERVE_r05.json": lambda d, ln: (
        "first serve-layer number (its own r07+ baseline)"),
    "BENCH_SERVE_DEVICE_r06.json": lambda d, ln: "{}x host engine at batch 8192 (same run)".format(
        d["device_speedup_vs_host"]["8192"]),
    "BENCH_DAEMON_r07.json": lambda d, ln: "{}x batch-1 engine (same run)".format(
        d["coalesced_speedup_vs_batch1"]),
    "BENCH_SERVE_V2_r09.json": lambda d, ln: (
        "{}x r05 AND qps (re-measured in-run); {}x v1 same-run".format(
            d["v2_vs_v1"]["boolean_and_vs_r05_baseline"],
            d["v2_vs_v1"]["boolean_and_speedup"])),
    "BENCH_RANKED_r11.json": lambda d, ln: "{}x r09 bm25 baseline (re-measured in-run)".format(
        round(d["value"] / d["baseline_r09_bm25_top10_qps"], 2)),
    "BENCH_SEGMENTS_r12.json": lambda d, ln: (
        "value IS the ratio: 16-segment AND qps vs the same run's "
        "single-artifact engine"),
    "BENCH_NATIVE_r16.json": lambda d, ln: (
        "{}x r11 ranked qps at submission group 32; {}x the same-run "
        "host engine at that group".format(
            d["speedup_vs_r11"], d["batches"]["32"]["speedup"])),
    "BENCH_WAL_r17.json": lambda d, ln: (
        "value IS the ratio: WAL-on mutation ack p99 vs the same "
        "run's WAL-off leg (budget {}x); replica catch-up {} MB/s"
        .format(d["gate"], d["replication"]["mb_per_s"])),
    "BENCH_CLUSTER_r18.json": lambda d, ln: (
        "{}x the same run's 1-core scaling envelope at D=4; hedged "
        "p99 {}x unhedged under a slow shard".format(
            round(d["sweep"]["4"]["cluster_pipelined"]["qps"]
                  / d["sweep"]["4"]["envelope_qps"], 2),
            round(d["hedge"]["hedged"]["p99_ms"]
                  / d["hedge"]["unhedged"]["p99_ms"], 2))),
    "BENCH_BUILD_OOC_r15.json": lambda d, ln: (
        "value IS the ratio: spill-tier wall vs the same run's "
        "in-memory build on a {}x-budget corpus (zero-spill {}x)"
        .format(d["gates"]["corpus_over_budget"],
                d["gates"]["zero_spill_overhead_x"])),
    "BENCH_BROWNOUT_r19.json": lambda d, ln: (
        "value IS the ratio: scatter RPCs per request*D under an "
        "intermittent overload (loose budget {}x; gate {}x); CoDel "
        "storm p99 {}x unloaded vs fixed-queue {}x".format(
            d["storm_amplification"]["loose"]["amplification"],
            d["amplification_gate"],
            d["storm"]["compliant_p99_x_unloaded"],
            round(d["storm"]["fixed_queue"]["compliant_p99_ms"]
                  / d["storm"]["unloaded"]["compliant_p99_ms"], 1))),
    "BENCH_QOS_r20.json": lambda d, ln: (
        "value IS the ratio: cached-hot qps vs the same run's "
        "uncached engine on one Zipf replay (gate {}x, byte-identical "
        "answers); paying-tenant p99 {}x alone beside a 2x-capacity "
        "tank (gate {}x; unfenced contrast {}x)".format(
            d["cache"]["gate"],
            d["isolation"]["paying_p99_x_alone"],
            d["isolation"]["gate"],
            d["isolation"]["unfenced_p99_x_alone"])),
}

_JSON_LINE_RE = re.compile(r"^\{.*\}$", re.M)


def _headline(data: dict) -> dict:
    """The metric/value/unit dict a bench file's schema leads with."""
    if "metric" in data and "value" in data:
        return data
    if isinstance(data.get("tail"), str):
        lines = _JSON_LINE_RE.findall(data["tail"])
        for text in reversed(lines):
            try:
                line = json.loads(text)
            except ValueError:
                continue
            if "metric" in line:
                return line
    for key in ("best_line", "tpu_line", "parsed"):
        line = data.get(key)
        if isinstance(line, dict) and "metric" in line:
            return line
    return {}


def _basis(name: str, data: dict, line: dict) -> str:
    fn = _BASIS.get(name)
    if fn is not None:
        try:
            return fn(data, line)
        except (KeyError, TypeError, ZeroDivisionError):
            pass
    for key in sorted(data):
        if key.startswith("gate_qps_"):
            return (f"priced in-run against the {key[len('gate_qps_'):]}"
                    f" gate ({data[key]} qps)")
    if isinstance(line.get("vs_baseline"), (int, float)):
        return (f"{line['vs_baseline']}x vs reference C baseline "
                f"(same run)")
    return "—"


def rows(root: Path = REPO_ROOT) -> list[dict]:
    out = []
    for path in sorted(root.glob("BENCH_*.json")):
        try:
            data = json.loads(path.read_text(encoding="utf-8"))
        except (OSError, ValueError) as e:
            print(f"bench-history: skipping {path.name}: {e}",
                  file=sys.stderr)
            continue
        line = _headline(data)
        m = _ROUND_RE.search(path.name)
        rnd = int(m.group(1)) if m \
            else _ROUND_OVERRIDES.get(path.name, 0)
        value = line.get("value", line.get("value_ms"))
        out.append({
            "file": path.name,
            "round": rnd,
            "metric": line.get("metric", "—"),
            "value": value if value is not None else "—",
            "unit": line.get("unit", "—"),
            "basis": _basis(path.name, data, line),
        })
    out.sort(key=lambda r: (r["round"], r["file"]))
    return out


def markdown_table(root: Path = REPO_ROOT) -> str:
    lines = ["| round | file | metric | value | unit | "
             "vs own-round baseline |",
             "|---|---|---|---|---|---|"]
    for r in rows(root):
        rnd = f"r{r['round']:02d}" if r["round"] else "—"
        lines.append(f"| {rnd} | `{r['file']}` | `{r['metric']}` | "
                     f"{r['value']} | {r['unit']} | {r['basis']} |")
    return "\n".join(lines)


def _split(text: str):
    try:
        head, rest = text.split(BEGIN, 1)
        block, tail = rest.split(END, 1)
    except ValueError:
        return None
    return head, block.strip(), tail


def check(root: Path = REPO_ROOT) -> int:
    readme = root / "README.md"
    if not readme.exists():
        print("bench-history: README.md not found", file=sys.stderr)
        return 1
    parts = _split(readme.read_text(encoding="utf-8"))
    if parts is None:
        print(f"bench-history: README.md lacks the {BEGIN} / {END} "
              f"markers", file=sys.stderr)
        return 1
    if parts[1] != markdown_table(root).strip():
        print("bench-history: README bench trajectory table is out of "
              "date — run `python tools/bench_history.py --write`",
              file=sys.stderr)
        return 1
    print("bench-history: README trajectory table in sync")
    return 0


def write(root: Path = REPO_ROOT) -> int:
    readme = root / "README.md"
    parts = _split(readme.read_text(encoding="utf-8"))
    if parts is None:
        print(f"bench-history: README.md lacks the {BEGIN} / {END} "
              f"markers — add them where the table should live",
              file=sys.stderr)
        return 2
    head, _, tail = parts
    readme.write_text(f"{head}{BEGIN}\n{markdown_table(root)}\n{END}"
                      f"{tail}", encoding="utf-8")
    print("bench-history: README trajectory table regenerated")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="bench_history",
        description="aggregate checked-in BENCH_*.json results into "
                    "one trajectory table (ratios against each "
                    "round's own baseline)")
    g = p.add_mutually_exclusive_group()
    g.add_argument("--check", action="store_true",
                   help="verify the README block matches (exit 1 on "
                        "drift); part of `make lint`")
    g.add_argument("--write", action="store_true",
                   help="regenerate the README block in place")
    args = p.parse_args(argv)
    if args.check:
        return check()
    if args.write:
        return write()
    print(markdown_table())
    return 0


if __name__ == "__main__":
    sys.exit(main())
