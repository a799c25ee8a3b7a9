"""Host input: host milliseconds per step spent building, collating and
blocking the step's bins (the benchmark's span around the trainer's
``_fetch_batch``)."""


def read(ctx):
    spans = (ctx["host_spans"] or {}).get("bench.fetch_batch")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
