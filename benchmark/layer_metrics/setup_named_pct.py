"""Share of ``setup_s`` that the program's set-up timeline names: the
union, over all threads, of every ``setup/*`` and ``xla/*`` interval
of ``observability.tracing.startup`` that lies inside the set-up, over
``setup_s``. What stays unnamed is the benchmark's own (imports,
``weights.maker``, the training check steps' steady steps, the serving
driver's ``ramp_s`` sleep) and whatever of the program no span covers.

How the set-up is bounded (the other four ``setup_*`` readers load
this file for it). ``run.py`` reads the clock in its first statement
and keeps it as ``__main__.T_START`` (``perf_counter`` seconds, the
tracer's clock); ``Session.window`` takes ``setup_s`` from that reading
as the window opens, and neither driver excludes reference work before
then. So the set-up is ``[T_START, T_START + setup_s]``, and an event
counts if it lies inside: one that began in the set-up and ended in
the window does not. Nothing to read (None) where ``__main__`` has no
``T_START`` (a test that drives ``main(argv)``: the buffer then holds
other tests' events too), where ``obs`` has no ``setup_s`` yet, or
where the program is older than the timeline. Prints the set-up by
span name, by function and its longest unnamed stretches."""

from benchmark.harness import xplane


def _interval(e):
    return e["t_ns"], e["t_ns"] + int(round(e["dur_us"] * 1e3))


def bounds(obs):
    """The set-up on the tracer's clock, ``(start, end)`` in ns, or
    None."""
    import sys
    t0 = getattr(sys.modules.get("__main__"), "T_START", None)
    setup_s = (obs.get("end_to_end") or {}).get("setup_s")
    if t0 is None or not setup_s:
        return None
    return int(t0 * 1e9), int((t0 + setup_s) * 1e9)


def setup_events(obs):
    """The timeline's events inside the set-up, or None."""
    try:
        from deeplearning4j_tpu.observability.tracing import startup
    except ImportError:
        return None
    if bounds(obs) is None:
        return None
    lo, hi = bounds(obs)
    return [e for e in startup.events()
            if lo <= e["t_ns"] and _interval(e)[1] <= hi]


def union_s(events, names):
    """Seconds some event whose name starts with one of ``names``
    covers, or None without a timeline."""
    if events is None:
        return None
    return xplane.length(xplane.union(
        _interval(e) for e in events
        if e["name"].startswith(names))) / 1e9


def _report(events, lo, hi):
    """The set-up by span name and by function, and its longest
    stretches that no span covers, as seconds after T_START."""
    by_name, by_fun = {}, {}
    for e in events:
        by_name.setdefault(e["name"], []).append(_interval(e))
        if e["name"].startswith("xla/"):
            row = by_fun.setdefault(e["args"]["fun_name"], [0.0, 0])
            row[0] += e["dur_us"] / 1e6
            row[1] += e["name"] == "xla/compile"
    print("setup: by span (union, s): " + ", ".join(
        f"{k} {xplane.length(xplane.union(v)) / 1e9:.2f} x{len(v)}"
        for k, v in sorted(by_name.items())), flush=True)
    top = sorted(by_fun.items(), key=lambda kv: -kv[1][0])[:8]
    print("setup: trace + lower + compile by function (s): "
          + ", ".join(f"{k} {v[0]:.2f} x{v[1]}" for k, v in top),
          flush=True)
    gaps = xplane.subtract([[lo, hi]], xplane.union(
        _interval(e) for e in events))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:6]
    print("setup: longest unnamed stretches (s after T_START): "
          + ", ".join(f"{(s - lo) / 1e9:.1f}-{(e - lo) / 1e9:.1f}"
                      for s, e in sorted(gaps)), flush=True)


def read(obs):
    events = setup_events(obs)
    named = union_s(events, ("setup/", "xla/"))
    if named is None:
        return None
    setup_s = obs["end_to_end"]["setup_s"]
    print(f"setup: {named:.2f} s named of setup_s {setup_s:.2f}",
          flush=True)
    if events:
        _report(events, *bounds(obs))
    return 100.0 * named / setup_s
