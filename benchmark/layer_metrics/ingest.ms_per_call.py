"""Host milliseconds a call spends staging and enqueueing its ingest: the
``ingest`` spans that start inside the measured window, averaged."""


def read(r):
    seconds, count = r.spans.total("ingest", *r.window)
    return seconds / count * 1e3 if count else None
