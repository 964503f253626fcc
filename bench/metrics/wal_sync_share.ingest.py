"""Share of the window spent in the WAL's group fsync: the program's
wal_sync spans (telemetry on in the traced run only), clipped to the window."""


def read(run):
    if not run.wal_spans:
        return None
    busy = sum(max(0.0, min(s + d, run.window_s) - max(s, 0.0)) for s, d in run.wal_spans)
    return 100.0 * busy / run.window_s
