"""Share of the first device's idle time that falls inside a span of
the program that has no child there, once the spans are on the trace's
clock. The offset is exact where the trace holds the tracer's anchor
event, else fitted from the run (harness/program_spans.py); a fit whose
per-step residuals spread over more than 1 ms gives no number. Prints
both offsets and the idle seconds by span name."""

from benchmark.harness import program_spans

MAX_SPREAD_NS = 1_000_000


def read(obs):
    tr, events = obs.get("trace"), program_spans.timed(obs)
    if tr is None or not events:
        return None
    exact = program_spans.anchor_offset(tr)
    fit = program_spans.fitted_offset(tr, events)
    if fit is not None:
        print(f"idle_named: fitted offset {fit['offset_ns']} ns over "
              f"{fit['steps']} steps ({fit['waits']} h2d_wait, "
              f"{fit['bursts']} device bursts, dropped "
              f"{fit['dropped']}); residuals spread "
              f"{fit['spread_ns'] / 1e3:.1f} us, worst "
              f"{fit['worst_ns'] / 1e3:.1f} us", flush=True)
    if exact is not None:
        offset = exact
        print(f"idle_named: exact offset {exact} ns from the anchor"
              + ("" if fit is None else
                 f"; fitted - exact = "
                 f"{(fit['offset_ns'] - exact) / 1e3:.1f} us"),
              flush=True)
    elif fit is not None and fit["spread_ns"] <= MAX_SPREAD_NS:
        offset = fit["offset_ns"]
    else:
        print("idle_named: no anchor event and no fit within 1 ms: "
              "nothing to read", flush=True)
        return None
    idle, split, named = program_spans.idle_by_span(tr, events, offset)
    if idle <= 0:
        return None
    print("idle_named: idle %.4f s of the first device; by span: %s"
          % (idle, ", ".join(f"{k} {v:.4f}" for k, v in sorted(
              split.items(), key=lambda kv: -kv[1]))), flush=True)
    return 100.0 * named / idle
