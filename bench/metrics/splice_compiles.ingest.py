"""Backend compiles inside the window made while a view_splice site was open
on the compiling thread (the program's compile spans; their ``sites``), so
the re-pads of dirty tiles inside a splice count with it; nothing to read
where no view was spliced in the window."""

from bench.harness.spans import overlap, spans


def read(run):
    w = run.window_s
    sp = spans("view_splice")
    if sp is None or not any(overlap(s, s + d, 0.0, w) > 0 for s, d, *_ in sp):
        return None
    comp = spans("compile") or []
    return float(sum(1 for s, d, _, a in comp
                     if "view_splice" in a.get("sites", ()) and s + d >= 0.0 and s <= w))
