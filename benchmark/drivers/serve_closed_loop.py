"""Driver of the closed-loop serving mixes: an in-process
``ModelServer`` on a live registered network, real HTTP on loopback,
``clients`` threads that each send their next ``/v1/generate`` when
the last answered.

Traffic file keys: ``server`` (``slots``, ``capacity``, ``page_size``,
``kv_mode``, ``queue_limit``), ``clients``, ``lengths`` (see
harness/inputs.py), ``ramp_s`` (clients run this long, after the
first answer, before the window opens: one longest request, so the
window samples a steady state), ``check_requests``, ``limits``,
``trace_after_s`` / ``trace_seconds``.

``correct`` compares, for ``check_requests`` requests the window
finished (drawn from the seed, the longest among them): the ids the
window served, held against the plain reference's best token at each
position; and the log-probabilities that the server's own paged
session gives at those positions when the same prompts and ids are
fed through it again with every slot in use, held against the
reference's distribution.
"""

import gc
import json
import threading
import time
import urllib.request

import numpy as np

from benchmark.harness import inputs, spec, stats, weights

MODEL = "lm"


class Clients:
    def __init__(self, base, lengths, config, seed, annotate):
        self.base, self.lengths, self.n = base, lengths, len(lengths)
        self.config, self.seed = config, seed
        self.annotate = annotate
        self.records, self.lock = [], threading.Lock()
        self.stop = threading.Event()
        self.threads = [threading.Thread(target=self._loop, args=(i,),
                                         daemon=True)
                        for i in range(self.n)]

    def start(self):
        for t in self.threads:
            t.start()

    def _one(self, prompt, n_tokens):
        body = json.dumps({"model": MODEL, "prompt": prompt,
                           "n_tokens": n_tokens}).encode()
        req = urllib.request.Request(
            self.base + "/v1/generate", body,
            {"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as resp:
            return json.loads(resp.read())["ids"]

    def _loop(self, i):
        import jax
        # client i cycles through its own share of the mix's fixed
        # set of sizes; the token ids are new each time
        mine, k = self.lengths[i], 0
        while not self.stop.is_set():
            length, n_tokens = mine[k % len(mine)]
            prompt = inputs.serve_prompt(self.config, self.seed, i, k,
                                         length)
            k += 1
            t0 = time.perf_counter()
            try:
                if self.annotate:
                    with jax.profiler.TraceAnnotation(
                            "bench/http_generate"):
                        ids = self._one(prompt, n_tokens)
                else:
                    ids = self._one(prompt, n_tokens)
                ok = len(ids) == n_tokens
            except Exception as e:      # refused or failed: it counts
                ids, ok = repr(e), False
            t1 = time.perf_counter()
            with self.lock:
                self.records.append((t0, t1, ok, n_tokens, prompt, ids))

    def finish(self, timeout):
        self.stop.set()
        for t in self.threads:
            t.join(timeout)
        if any(t.is_alive() for t in self.threads):
            raise RuntimeError("a client thread did not end")


def replay_log_probs(session, records):
    """What the server's own paged session (``step_slots``: the
    compiled step the window drove, every slot in use) gives at each
    served position of ``records``: their prompts and served ids fed
    token by token, ``session.slots`` requests at a time. Returns,
    per record, (served tokens, V) float64 log-probabilities."""
    out = []
    for g in range(0, len(records), session.slots):
        group = records[g:g + session.slots]
        # free every slot and forget cached prefixes, in the pools the
        # window used (a second pool beside them need not fit)
        session.release_all()
        session.prefix_cache.clear()
        seqs = [list(r[4]) + list(r[5]) for r in group]
        for i, r in enumerate(group):
            session.bind(i, session.reserve(r[4], len(r[5])))
        rows = [[] for _ in group]
        for t in range(max(len(q) for q in seqs) - 1):
            x = np.zeros((session.slots, 1, 1), np.float32)
            active = np.zeros((session.slots,), bool)
            for i, q in enumerate(seqs):
                if t < len(q) - 1:
                    x[i, 0, 0], active[i] = q[t], True
            h = np.asarray(session.step_slots(x, active))
            for i, (r, q) in enumerate(zip(group, seqs)):
                if len(r[4]) - 1 <= t < len(q) - 1:
                    # a probability that underflowed float32 is not
                    # log 0: floor it at the smallest normal number
                    rows[i].append(np.log(np.maximum(
                        h[i, 0], np.finfo(np.float32).tiny,
                        dtype=np.float64)))
        out += [np.stack(r) for r in rows]
    session.release_all()
    return out


def log_softmax(z):
    z = np.asarray(z, np.float64)
    m = z.max(axis=-1, keepdims=True)
    return z - m - np.log(np.exp(z - m).sum(axis=-1, keepdims=True))


def served_positions(rec):
    prompt, ids = rec[4], rec[5]
    return np.arange(len(prompt) - 1, len(prompt) + len(ids) - 1)


def reference_numbers(ref, config, params, records, pad_to, served_logp,
                      tokens=None):
    """One pass of the reference over each record's prompt and served
    ids. ``gap``: the widest distance by which a served token's
    reference logit lies below the reference's best at its position
    (``tokens``, per record, stand in for the served ids where a
    control's first choices are read). ``kl``: the mean, over served
    positions, of the divergence from the reference's next-token
    distribution to the one in ``served_logp`` (what stands in the
    program's place). ``tokens``: how many were compared."""
    gap, kl, n = 0.0, 0.0, 0
    for k, rec in enumerate(records):
        prompt, ids = rec[4], rec[5]
        seq = np.zeros((pad_to,), np.int32)
        seq[:len(prompt) + len(ids)] = list(prompt) + list(ids)
        z = np.asarray(ref.logits(params, seq, config))[
            served_positions(rec)]
        tok = ids if tokens is None else tokens[k]
        gap = max(gap, float((z.max(axis=-1)
                              - z[np.arange(len(tok)), tok]).max()))
        lp = log_softmax(z)
        kl += float((np.exp(lp) * (lp - served_logp[k])).sum())
        n += len(ids)
    return {"gap": gap, "kl": kl / n, "tokens": n}


def sample_records(done, k, seed):
    """The longest finished request and ``k - 1`` others drawn from
    the seed."""
    done = sorted(done, key=lambda r: r[0])
    longest = max(done, key=lambda r: len(r[4]) + len(r[5]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 3])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)),
                      replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def start_server(net, traffic):
    from deeplearning4j_tpu.serving.http import ModelServer
    from deeplearning4j_tpu.serving.metrics import ServingMetrics
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    sv = traffic["server"]
    registry = ModelRegistry()
    registry.register(MODEL, net)
    server = ModelServer(
        registry, port=0, host="127.0.0.1", slots=sv["slots"],
        capacity=sv["capacity"], page_size=sv["page_size"],
        kv_mode=sv["kv_mode"], queue_limit=sv["queue_limit"],
        metrics=ServingMetrics(), sample_rate=0.0)
    server.start()
    return server


def run(s, break_token=None):
    import jax
    config, traffic = s.cell.config, s.cell.traffic
    builder = spec.load_module("builders", config["builder"])
    ref = spec.load_module("reference", config["reference"])
    lengths = inputs.serve_lengths(traffic, s.seed, traffic["clients"])
    with builder.policy(config):
        net = builder.build(config).init()
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), net.params)
        maker = weights.maker(shapes, config["init"])
        make_params = lambda: maker(s.seed31())
        net.params = make_params()
        server = start_server(net, traffic)
        base = f"http://127.0.0.1:{server.port}"
        clients = Clients(base, lengths, config, s.seed, s.trace)
        try:
            if break_token is not None:
                break_token(server)
            clients.start()
            # the first answer proves the step program is compiled or
            # loaded; only then does the ramp's clock start
            while not clients.records:
                time.sleep(0.05)
            session = server.batcher_for(MODEL)[0].session
            time.sleep(traffic["ramp_s"])
            registry = server.metrics.registry
            with s.window():
                t_open = time.perf_counter()
                s.obs["counters"]["before"] = registry.snapshot()
                if s.trace:
                    time.sleep(traffic["trace_after_s"])
                    s.trace_start()
                    time.sleep(traffic["trace_seconds"])
                    s.trace_stop()
                time.sleep(max(0.0, t_open + s.seconds
                               - time.perf_counter()))
                s.obs["counters"]["after"] = registry.snapshot()
                t_close = time.perf_counter()
            clients.finish(timeout=300)
        finally:
            clients.stop.set()
            stopped = server.stop(drain=True, timeout=120.0)
        if not stopped:
            raise RuntimeError("server.stop(drain=True) did not drain")
        if s.trace:
            s.trace_reduce()

    w = stats.window_summary(clients.records, t_open, t_close)
    done = [r for r in stats.in_window(clients.records, t_open, t_close)
            if r[2]]
    print(f"window: {w['attempted']} requests completed, "
          f"{w['failed']} failed, in {t_close - t_open:.3f} s",
          flush=True)
    s.check("failed_requests", w["failed"], 0.0)
    if done:
        L, limits = traffic["lengths"], traffic["limits"]
        sample = sample_records(done, traffic["check_requests"], s.seed)
        pad_to = L["prompt_max"] + L["output_max"]
        t0 = time.perf_counter()
        served_logp = replay_log_probs(session, sample)
        print(f"replayed {len(sample)} sampled requests through the "
              f"server's session in {time.perf_counter() - t0:.2f} s",
              flush=True)
        s.obs["check_sample"] = (sample, pad_to, make_params,
                                 served_logp)
        del server, net, session
        gc.collect()
        with s.excluded("reference logits of sampled requests"):
            got = reference_numbers(ref, config, make_params(), sample,
                                    pad_to, served_logp)
        print(f"reference: compared {got['tokens']} served tokens",
              flush=True)
        s.check("served_token_widest_logit_gap", got["gap"],
                limits["served_token_widest_logit_gap"])
        s.check("served_logprob_kl", got["kl"],
                limits["served_logprob_kl"])
    else:
        s.check("requests_completed_in_window", 0.0, -1.0)
    s.obs["latencies_ms"] = w["latencies_ms"]
    return s.result(w["attempted"], w["failed"],
                    {"serve_tokens_per_s": w["tokens_per_s"]})
