"""Mean host time of one admission (batch-1 prefill, the cache scatter
into its slot and the first token's fetch) in the window, from the
harness's span around ``ServeEngine._insert``, which ends at a sync."""


def read(run, cell):
    spans = run.layer.get("admission_s") or []
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
