"""Seconds of set-up the program itself recorded: spans of its process
``SpanRecorder`` (``spans``: names, the last span of each, summed; each placed
on the harness's clock, which starts at ``run.py``'s first line, on an earlier
line), or the stage
fields of one entry of its compilation ledger (``entry`` and ``fields``).
Only what ended before the timed window began is counted.  A program without
the span, the recorder's origin or the fields reports nothing."""


def read(ctx, spans=(), entry=None, fields=()):
    try:
        if entry is not None:
            from apex_tpu.observability import compilation
            record = compilation.get_ledger().snapshot()["entries"][entry]
            stages = {k: record[k] for k in (*compilation.STAGE_FIELDS, "compile_wall_s", "cache")}
            return {"value": sum(float(record[f]) for f in fields), **stages}
        from apex_tpu.observability import get_recorder
        recorder = get_recorder()
        origin, events = recorder.origin, recorder.events()
    except (ImportError, AttributeError, KeyError):
        return None
    setup_end = ctx.cell.t_start + ctx.facts.get("setup_s", float("inf"))
    found = {}
    for ev in events:
        begin, seconds = origin + ev["ts"] / 1e6, ev.get("dur", 0.0) / 1e6
        if ev["name"] in spans and ev.get("ph") == "X" and begin + seconds <= setup_end:
            found[ev["name"]] = {"begin_s": begin - ctx.cell.t_start, "s": seconds}
    if set(found) != set(spans):
        return None
    return {"value": sum(f["s"] for f in found.values()), "spans": found}
