"""Driver of the training mixes: ``fit(iterator)`` over a seeded pool
of distinct batches, cycled, each batch crossing host->device as
``fit`` does it, with the program's ``CollectScoresIterationListener``
attached at its default frequency of 1.

That listener is part of the traffic, not of the yardstick: it reads
the score on the host after every iteration, as upstream DL4J's
``fit`` does by construction and as ``cli train --health`` does here
(one scalar fetch a step), so the host cannot run ahead of the device
and the next batch's copy does not overlap the running step. A user
who attaches no listener, or one every 10 iterations, does not pay
that; such a mix needs a driver of its own (PERF.md, open questions).

Traffic file keys: ``batch`` (global), ``pool_batches``, ``inputs``
(see harness/inputs.py), ``fit_kwargs`` (passed to ``fit``, e.g.
``{"mesh_spec": "dp=4"}``), ``sample`` (what one sample is),
``check_steps`` (steps the reference follows), ``limits``,
``trace_after_s`` / ``trace_seconds`` (the traced part of a
``--trace 1`` window), ``trace_host_level`` (the profiler's host
level, 2 unless given).
"""

import gc
import time

import numpy as np

from benchmark.harness import inputs, spec, train_check, weights


def _iterator_class():
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import DataSetIterator

    class PoolIterator(DataSetIterator):
        """Cycles the pool. One ``fit`` call consumes ``steps`` batches
        or runs until ``deadline``; the cursor survives ``reset`` so
        the set-up steps and the window walk one sequence."""

        def __init__(self, pool):
            self.pool = [DataSet(x, y) for x, y in pool]
            self.cursor = 0
            self.steps, self.deadline = None, None
            self.on_step = None

        def reset(self):
            pass

        def _iterate(self):
            n = 0
            while True:
                if self.steps is not None and n >= self.steps:
                    return
                if (self.deadline is not None
                        and time.perf_counter() >= self.deadline):
                    return
                if self.on_step is not None:
                    self.on_step(n)
                ds = self.pool[self.cursor % len(self.pool)]
                self.cursor += 1
                n += 1
                yield ds

    return PoolIterator


class _Tracing:
    """Starts and stops the profiler at step boundaries and keeps one
    host annotation open over each step the program runs."""

    def __init__(self, session, after_s, for_s):
        self.s, self.after_s, self.for_s = session, after_s, for_s
        self.t0, self.state, self.ann = None, "before", None
        self.steps = 0

    def __call__(self, n):
        import jax
        now = time.perf_counter()
        if self.t0 is None:
            self.t0 = now
        self._close()
        if self.state == "on":
            self.steps += 1          # a step ended under the profiler
            if now - self.t_on >= self.for_s:
                self._stop(now)
        elif self.state == "before" and now - self.t0 >= self.after_s:
            self.s.trace_start()
            self.state, self.t_on = "on", now
            self.t_first = time.perf_counter()
        if self.state == "on":
            self.ann = jax.profiler.TraceAnnotation(
                "bench/step_in_program")
            self.ann.__enter__()

    def _close(self):
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None

    def _stop(self, now):
        # steps that began and ended while the profiler was on
        self.s.obs["traced"] = {"steps": self.steps,
                                "seconds": now - self.t_first}
        self.s.trace_stop()
        self.state = "done"

    def finish(self):
        self._close()
        if self.state == "on":
            self.steps += 1
            self._stop(time.perf_counter())


def program_numbers(net, it, rec, fit_kwargs, config, make_params,
                    n_steps):
    """Drive the first steps through the window's own call and feed
    and read what ``correct`` compares."""
    import jax
    import jax.numpy as jnp
    norms = jax.jit(weights.leaf_norms)
    first = config["assumed"]["first_gradient"]
    g1 = None
    for i in range(n_steps):
        it.steps = 1
        net.fit(it, **fit_kwargs)
        if i == 0:
            g1 = np.asarray(norms(train_check.find_state_field(
                net.opt_state, first["state_field"]))) * first["scale"]
    it.steps = None
    delta = np.asarray(jax.jit(lambda p, q: weights.leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, p, q)))(
        net.params, make_params()))
    return {"losses": [v for _, v in rec.scores[-n_steps:]],
            "grad_norms": g1, "delta_norms": delta}


def run(s, break_step=None):
    import jax
    from deeplearning4j_tpu.observability.tracing import trace
    from deeplearning4j_tpu.train.listeners import (
        CollectScoresIterationListener)
    config, traffic = s.cell.config, s.cell.traffic
    builder = spec.load_module("builders", config["builder"])
    ref = spec.load_module("reference", config["reference"])
    pool = inputs.train_pool(traffic, config, s.seed)
    n_check = traffic["check_steps"]
    fit_kwargs = dict(traffic.get("fit_kwargs", {}))

    with builder.policy(config):
        seq = traffic["inputs"].get("seq_len")
        shapes = jax.eval_shape(
            lambda: builder.build(config, seq).init().params)
        maker = weights.maker(shapes, config["init"])
        make_params = lambda: maker(s.seed31())
        batches = [ref.batch_of(*pool[i % len(pool)])
                   for i in range(n_check)]

        net = builder.build(config, seq).init()
        net.params = make_params()
        rec = CollectScoresIterationListener()    # every iteration
        net.set_listeners(rec)
        it = _iterator_class()(pool)
        if break_step is not None:
            break_step(net)
        got = program_numbers(net, it, rec, fit_kwargs, config,
                              make_params, n_check)

        tracing = None
        if s.trace:
            s.program_tracer = trace
            tracing = _Tracing(s, traffic["trace_after_s"],
                               traffic["trace_seconds"])
            it.on_step = tracing
        first_window_step = it.cursor
        with s.window():
            t0 = time.perf_counter()
            it.deadline = t0 + s.seconds
            net.fit(it, **fit_kwargs)
            jax.block_until_ready(net.params)
            t1 = time.perf_counter()
        steps = it.cursor - first_window_step
        if tracing is not None:
            tracing.finish()
            s.trace_reduce()

        # the plain reference, once the program's state is freed: the
        # peak read at the window's close is the program's alone
        del net, it
        gc.collect()
        with s.excluded(f"{n_check} reference steps"):
            want = train_check.reference_steps(ref, config, make_params,
                                               batches)
        train_check.compare(s, got, want, traffic["limits"])

    losses = [v for _, v in rec.scores[first_window_step:]]
    every = len(pool)
    finite = (bool(np.all(np.isfinite(losses))) and steps > 0
              and len(losses) == steps)
    s.check("window_losses_not_finite", 0.0 if finite else 1.0, 0.0)
    if steps >= 2 * every:
        rise = (np.mean(losses[-every:]) - np.mean(losses[:every]))
        s.check("window_loss_rise_last_pass_over_first", rise, 0.0)
    chips = len(s.devices)
    rate = steps * traffic["batch"] / (t1 - t0) / chips
    s.obs["samples_per_step"] = traffic["batch"]
    print(f"window: {steps} steps of {traffic['batch']} in "
          f"{t1 - t0:.3f} s on {chips} chip(s); losses "
          f"{losses[:2]} .. {losses[-2:]}", flush=True)
    return s.result(steps, 0 if finite else steps,
                    {"train_samples_per_s_per_chip": rate})
