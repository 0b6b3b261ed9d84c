"""What ``correct`` compares in a training cell, and the plain
optimizers the reference follows its first steps with.

The program's numbers come from the very object the window then
drives: each of the first steps' losses, the first gradient as the
optimizer got it (read back from its state after one step), and the
parameters' change after the steps. The reference's come from
``reference/<name>.py`` and the update rules below, written from the
published formulas -- nothing of the program, nothing of optax.
"""

import functools
import time

import numpy as np

from . import weights


# ---- update rules (Kingma & Ba 2015 alg. 1; Sutskever et al. 2013
#      Nesterov momentum in the form optax.sgd(nesterov=True) uses)

def _adam(p, g, st, t, a):
    b1, b2, eps = a.get("beta1", 0.9), a.get("beta2", 0.999), a.get(
        "eps", 1e-8)
    m = b1 * st[0] + (1 - b1) * g
    v = b2 * st[1] + (1 - b2) * g * g
    mhat, vhat = m / (1 - b1 ** t), v / (1 - b2 ** t)
    return p - a["learning_rate"] * mhat / (vhat ** 0.5 + eps), (m, v)


def _nesterovs(p, g, st, t, a):
    mu = a.get("momentum", 0.9)
    tr = g + mu * st[0]
    return p - a["learning_rate"] * (g + mu * tr), (tr,)


RULES = {"adam": (_adam, 2), "nesterovs": (_nesterovs, 1)}


def reference_steps(ref, config, make_params, batches, control=False):
    """Follow ``len(batches)`` steps. ``make_params()`` makes the
    seeded weights anew (they are not kept twice). ``control=True``
    computes in the precision below the configuration's, as the
    reference file defines it (benchmark/tests only). Returns losses,
    per-leaf norms of the first gradient and of the parameters'
    change, as numpy."""
    import jax
    import jax.numpy as jnp
    rule, n_slots = RULES[config["assumed"]["updater"]]
    a = config["assumed"]
    cast = ref.control_cast if control else (lambda t: t)
    params = cast(make_params())
    state = [jax.tree_util.tree_map(jnp.zeros_like, params)
             for _ in range(n_slots)]

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def update(params, grads, state, t):
        flat_p, tree = jax.tree_util.tree_flatten(params)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_s = [jax.tree_util.tree_leaves(s) for s in state]
        new_p, new_s = [], [[] for _ in state]
        for i, (p, g) in enumerate(zip(flat_p, flat_g)):
            # t stays float32: in a lower storage type 0.999 ** t is 1
            # and the bias correction divides by zero; what is stored
            # (parameters, moments) keeps the parameters' type
            q, st = rule(p, g.astype(p.dtype),
                         tuple(s[i] for s in flat_s), t, a)
            new_p.append(q.astype(p.dtype))
            for k, x in enumerate(st):
                new_s[k].append(x.astype(p.dtype))
        un = functools.partial(jax.tree_util.tree_unflatten, tree)
        return un(new_p), [un(s) for s in new_s]

    norms = jax.jit(weights.leaf_norms)
    losses, g1 = [], None
    clock = time.perf_counter()
    for i, batch in enumerate(batches):
        loss, grads = ref.loss_and_grads(params, batch, config,
                                         control=control)
        if i == 0:
            g1 = np.asarray(norms(grads))
        losses.append(float(loss))
        params, state = update(params, grads, state,
                               jnp.asarray(i + 1, jnp.float32))
        del grads
        print(f"reference: step {i + 1} loss {losses[-1]:.6f} at "
              f"{time.perf_counter() - clock:.2f} s", flush=True)
    del state
    delta = np.asarray(jax.jit(lambda p, q: weights.leaf_norms(
        jax.tree_util.tree_map(lambda x, y: x.astype(jnp.float32)
                               - y.astype(jnp.float32), p, q)))(
        params, cast(make_params())))
    return {"losses": losses, "grad_norms": g1, "delta_norms": delta}


def find_state_field(opt_state, field):
    """The first optimizer-state entry with attribute ``field``."""
    import jax
    stack = [opt_state]
    while stack:
        node = stack.pop()
        if hasattr(node, field) and hasattr(node, "_fields"):
            return getattr(node, field)
        if isinstance(node, (tuple, list)):
            stack.extend(reversed(node))
        elif isinstance(node, dict):
            stack.extend(node.values())
    raise KeyError(f"optimizer state has no field {field!r}")


def worst_leaf_gap(got, want):
    """max over leaves of |‖got‖ - ‖want‖| / max(‖want‖, median ‖want‖):
    the gap between norms, not the norm of a difference; the median
    guards leaves whose gradient is all but zero."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    denom = np.maximum(want, np.median(want))
    gaps = np.abs(got - want) / denom
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def compare(session, got, want, limits):
    """Print and record every compared number beside its limit."""
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        session.check(f"loss_step{i + 1}_rel_gap", abs(a - b) / abs(b),
                      limits["loss_rel_gap"])
    g, leaf = worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    session.check(f"first_grad_norm_worst_leaf_gap(leaf {leaf})", g,
                  limits["grad_norm_worst_leaf_gap"])
    d, leaf = worst_leaf_gap(got["delta_norms"], want["delta_norms"])
    session.check(f"param_change_norm_worst_leaf_gap(leaf {leaf})", d,
                  limits["param_change_worst_leaf_gap"])
