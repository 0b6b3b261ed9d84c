"""One run of one cell: the device, the clock, the measured window,
the optional profiler trace, the comparisons behind ``correct`` and
the result line."""

import contextlib
import json
import os
import shutil
import sys
import time

from . import spec as spec_mod


def find_devices(chips):
    """The TPU devices this cell asks for, or exit non-zero."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"benchmark: no accelerator: jax found {devs[0].platform} "
              f"({devs[0].device_kind})", file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"benchmark: cell needs {chips} chips, jax found "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips]


class Session:
    def __init__(self, cell, seed, seconds, trace, t_start,
                 find=find_devices):
        self.cell, self.seed = cell, int(seed)
        self.seconds, self.trace = float(seconds), bool(trace)
        self.t_start = t_start
        self.excluded_s = 0.0          # reference work, not set-up
        self.setup_s = None
        self.checks = []
        self.obs = {"cell": cell, "spans": [], "counters": {}}
        self.devices = find(cell.chips)
        from deeplearning4j_tpu.observability.compile_watch import (
            install_global_watch)
        from deeplearning4j_tpu.util.platform import setup_compile_cache
        self.cache_dir = setup_compile_cache(
            os.path.join(spec_mod.ROOT, ".jax_cache"))
        self.compiles = install_global_watch()
        self._trace_dir = os.path.join(
            spec_mod.ROOT, ".bench_trace", f"{cell.name}.{os.getpid()}")
        self.program_tracer = None     # set by a driver that reads spans

    # ---- seeds: any whole number, folded under 2**31
    def seed31(self):
        import numpy as np
        return int(np.random.SeedSequence(
            [self.seed, 0]).generate_state(1)[0] >> 1)

    # ---- the clock
    @contextlib.contextmanager
    def excluded(self, what):
        """Work for ``correct`` (the plain reference): timed apart and
        taken out of ``setup_s``."""
        t0 = time.perf_counter()
        yield
        dt = time.perf_counter() - t0
        if self.setup_s is None:
            self.excluded_s += dt
        print(f"reference: {what} took {dt:.2f} s (not in setup_s)",
              flush=True)

    @contextlib.contextmanager
    def window(self):
        """Everything before is set-up. Nothing may compile inside."""
        summary = self.compiles.summary()
        self.obs["setup_compile"] = summary
        print(f"set-up: {summary}", flush=True)
        self.setup_s = (time.perf_counter() - self.t_start
                        - self.excluded_s)
        with self.compiles.zero_compile_scope(
                f"measured window of {self.cell.name}"):
            yield
        self.peak_bytes = self.peak_bytes_now()
        print("memory at the window's close: " + str([
            {k: v for k, v in (d.memory_stats() or {}).items()
             if k.startswith(("peak", "bytes_in", "bytes_res"))}
            for d in self.devices]), flush=True)

    def peak_bytes_now(self):
        """Peak bytes on the fullest chip: the peak of live arrays
        plus the peak reserved for programs' temporaries, which the
        TPU runtime counts apart (a step's 7.7 GB of temporaries show
        in ``peak_bytes_reserved`` only). 0 where the backend keeps no
        such counts, which a TPU does."""
        stats = [d.memory_stats() or {} for d in self.devices]
        return max(st.get("peak_bytes_in_use", 0)
                   + st.get("peak_bytes_reserved", 0) for st in stats)

    # ---- the profiler trace (a short steady part of the window)
    def trace_start(self):
        import jax
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        os.makedirs(self._trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the host's own frames cost
        # 2: runtime events and TraceAnnotations; a mix whose runtime
        # floods the host's trace asks for 0 (the device alone) in its
        # traffic file
        opts.host_tracer_level = int(
            self.cell.traffic.get("trace_host_level", 2))
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        if self.program_tracer is not None:
            self.program_tracer.enable()

    def trace_stop(self):
        import jax
        if self.program_tracer is not None:
            # the program's spans of the traced, steady part only
            self.obs["spans"] = self.program_tracer.events()
            self.program_tracer.disable()
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        print(f"trace: stopping the profiler took "
              f"{time.perf_counter() - t0:.2f} s", flush=True)

    def trace_reduce(self):
        """After the window: parse the xplane into plain lists."""
        from . import xplane
        t0 = time.perf_counter()
        try:
            self.obs["trace"] = tr = xplane.load_dir(self._trace_dir)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
        print(f"trace: {sum(len(d['ops']) for d in tr['devices'])} "
              f"device ops, {len(tr['host'])} host events, read in "
              f"{time.perf_counter() - t0:.2f} s", flush=True)

    # ---- correct
    def check(self, name, value, limit, ok=None):
        """One compared number beside its limit (value <= limit)."""
        value = float(value)
        if ok is None:
            ok = value == value and value <= limit
        self.checks.append({"name": name, "value": value,
                            "limit": limit, "ok": bool(ok)})
        print(f"check {name}: value {value:.6g} limit {limit:.6g} "
              f"{'ok' if ok else 'NOT OK'}", flush=True)
        return ok

    @property
    def correct(self):
        return bool(self.checks) and all(c["ok"] for c in self.checks)

    # ---- the result line
    def result(self, attempted, failed, values):
        dev = self.devices[0]
        values = dict(values, setup_s=self.setup_s)
        out = {"correct": self.correct, "attempted": int(attempted),
               "failed": int(failed), "metrics": {},
               "device": {"platform": dev.platform,
                          "kind": dev.device_kind,
                          "count": len(self.devices),
                          "memory_peak_bytes": int(self.peak_bytes)}}
        if not self.trace:
            for m in self.cell.end_to_end:
                out["metrics"][m["name"]] = {
                    "value": float(values[m["name"]]), "unit": m["unit"]}
            return out
        self.obs["end_to_end"] = values
        self.obs["device"] = dev
        self.obs["n_devices"] = len(self.devices)
        self.obs["peak_bytes"] = self.peak_bytes
        tr = self.obs.get("trace")
        if tr is not None:
            from . import xplane
            busy, span = xplane.busy_and_window(tr)
            out["device"]["busy_s"] = busy
            out["device"]["window_s"] = span
            out["breakdown"] = xplane.breakdown(tr)
        for m in self.cell.per_layer:
            reader = spec_mod.load_module("layer_metrics", m["name"])
            if reader is None:
                raise SystemExit(f"per-layer metric {m['name']} has no "
                                 f"reader benchmark/layer_metrics/")
            v = reader.read(self.obs)
            if v is not None:
                out["metrics"][m["name"]] = {"value": float(v),
                                             "unit": m["unit"]}
        return out


def emit(result):
    print(json.dumps(result), flush=True)
