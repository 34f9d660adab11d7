"""persist_ms: the invariant cache's persistence per request: over the
traced window's requests, the sum of the daemon's ``engine.save_cache``
spans (a journal segment with its fsync, or a whole-store compaction)
over the number of ``client.price`` spans (``repro.obs`` spans; see
``bench/harness/spans.py``).  The note counts compactions and says what
the slow requests hold."""
from bench.harness import spans


def read(run):
    w = spans.window(run)
    if w is None:
        return None
    comp, fsync = w["compactions"], w["fsync_ms"]
    note = (f"{len(comp)} cache.compaction spans"
            + (f", first {comp[0]!r} ms, last {comp[-1]!r} ms, max "
               f"{max(comp)!r} ms, total {sum(comp)!r} ms" if comp else "")
            + f"; {len(fsync)} durable.append fsyncs"
            + (f", mean {sum(fsync) / len(fsync)!r} ms, max {fsync[-1]!r}"
               f" ms" if fsync else "")
            + "; " + spans.tail_note(w))
    return w["persist_ms"], note
