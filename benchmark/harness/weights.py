"""Weights from the seed, made by the benchmark (not by the program)
on the device in ONE jitted call, in the type they are served in.

The rule is by leaf, from the configuration file's ``init``:
``{"matrix": "normal:0.02" | "he_fan_in", "vector": "normal:0.02" |
"zeros", "matrix2d": <rule for 2-D leaves, optional>, "gains":
["ln1_g", ...]}``. A leaf whose last path key is in
``gains`` is all ones; other 1-D leaves follow ``vector``; the rest
follow ``matrix``. The same call serves the program and the plain
reference, so neither takes anything the other has made.
"""

import math


def _leaf_name(path):
    k = path[-1]
    return str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", k))))


def _std(rule, shape):
    if rule == "zeros":
        return 0.0
    if rule.startswith("normal:"):
        return float(rule.split(":")[1])
    if rule == "he_fan_in":
        fan_in = 1
        for n in shape[:-1]:
            fan_in *= n
        return math.sqrt(2.0 / fan_in)
    raise ValueError(f"unknown init rule {rule!r}")


def maker(shapes, init):
    """``shapes``: a pytree of arrays or ShapeDtypeStructs (only shape
    and dtype are read). Returns ``make(seed31) -> the same tree,
    filled from the seed``; one traced program serves every call."""
    import jax
    import jax.numpy as jnp
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    plan = []
    for path, leaf in flat:
        name, shape = _leaf_name(path), tuple(leaf.shape)
        if name in init.get("gains", ()):
            plan.append((shape, leaf.dtype, None))
        else:
            rule = (init["vector"] if len(shape) <= 1 else
                    init.get("matrix2d", init["matrix"])
                    if len(shape) == 2 else init["matrix"])
            plan.append((shape, leaf.dtype, _std(rule, shape)))

    @jax.jit
    def gen(key):
        out = []
        for i, (shape, dtype, std) in enumerate(plan):
            if std is None:
                out.append(jnp.ones(shape, dtype))
            elif std == 0.0:
                out.append(jnp.zeros(shape, dtype))
            else:
                out.append((jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                    * std).astype(dtype))
        return out

    return lambda seed31: jax.tree_util.tree_unflatten(
        treedef, gen(jax.random.PRNGKey(seed31)))


def leaf_norms(tree):
    """Per-leaf L2 norms as one float32 vector (jit this)."""
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree_util.tree_leaves(tree)])
