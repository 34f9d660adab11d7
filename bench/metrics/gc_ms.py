"""gc_ms: Python's collector per request: the sum of every ``gc.collect``
span that starts inside the traced window, in the client and in the
daemon, over the number of ``client.price`` spans (``repro.obs`` spans;
see ``bench/harness/spans.py``).  The note splits it by process and
generation."""
from bench.harness import spans


def read(run):
    w = spans.window(run)
    if w is None:
        return None
    split: dict = {}
    for client, gen, ms in w["gc"]:
        key = f"{'client' if client else 'daemon'} gen {gen}"
        count, total, top = split.get(key, (0, 0.0, 0.0))
        split[key] = (count + 1, total + ms, max(top, ms))
    note = "; ".join(f"{k}: {c} collections, {t!r} ms, max {m!r} ms"
                     for k, (c, t, m) in split.items()) or "no collections"
    where = sorted(w["gc_where"].items(), key=lambda kv: -kv[1])
    if where:
        note += "; by the span it paused: " + ", ".join(
            f"{k} {ms!r} ms" for k, ms in where)
    return w["gc_ms"], note
