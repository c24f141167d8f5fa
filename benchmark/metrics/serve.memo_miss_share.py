"""Share of the window's per-term BM25 memo lookups that missed
(``stats.engine.bm25_memo``, counted over the device engine's
contribution and block-bound memos): each miss decodes the term's whole
posting list on the host."""

from benchmark.snapshot import delta


def read(run):
    hits = delta(run, "engine", "bm25_memo", "hits")
    misses = delta(run, "engine", "bm25_memo", "misses")
    if hits is None or misses is None or not hits + misses:
        return None
    return misses / (hits + misses) * 100.0
