"""Host milliseconds per job inside the program's `unigps.prepare` span
(`build_device_graph`: layouts, window table, upload; repro/obs.py),
timed by the program. The span totals are process-wide, so they hold the
set-up's warm-up job as well as the window's; every job of a cell
prepares the same graph. Moves `evps`."""


def read(run):
    try:
        from repro import obs
    except ImportError:  # a program without layer names
        return None
    count, seconds = obs.span_totals().get(obs.PREPARE, (0, 0.0))
    if not count:
        return None
    return 1000.0 * seconds / count
