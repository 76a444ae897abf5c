"""The plain side of the comparison that decides ``correct`` for a
training cell. Nothing here imports the program.

- ``make_weights``: every leaf of a configuration from ``--seed``, on the
  device, in one jitted call. The harness hands a copy to the program and
  the reference makes its own from the same seed.
- ``make_dot``: the matrix product every reference model is written over.
  ``"f32"`` is float32 at ``highest`` precision (the reference proper);
  ``"fp8"`` rounds both operands, and in the backward pass the cotangent,
  to float8_e4m3 with one scale per tensor: the nearest precision below the
  bf16 the configurations state, and the control that must come out as not
  correct.
- ``follow``: the first steps of training in float32, the batch taken in
  blocks of rows so that it fits beside nothing else on the chip, under the
  plain form of the optimizer the traffic names. ``rows`` plants the
  half-batch fault: only those rows, the mean taken over them.
- ``compare``: the numbers compared, each beside its limit.
"""
import functools
import math
import statistics

import jax
import jax.numpy as jnp

#: a leaf whose first reference gradient is under this share of the median
#: leaf's moves by round-off alone under Adam: left out of ``change3``
DEAD_GRADIENT_SHARE = 1e-3


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_weights(spec: list, seed: int) -> dict:
    """``spec``: ``[(name, shape, kind, scale)]`` with kind ``normal``,
    ``uniform`` (in +-scale) or ``gamma`` (1 + normal). float32, the type the program
    keeps its master weights in."""
    def gen(key):
        out = {}
        for i, (name, shape, kind, scale) in enumerate(spec):
            k = jax.random.fold_in(key, i)
            if kind == "normal":
                out[name] = scale * jax.random.normal(k, shape, jnp.float32)
            elif kind == "uniform":
                out[name] = jax.random.uniform(k, shape, jnp.float32,
                                               -scale, scale)
            elif kind == "gamma":
                out[name] = 1 + scale * jax.random.normal(k, shape,
                                                          jnp.float32)
            else:
                raise ValueError(f"unknown init kind {kind!r}")
        return out
    return jax.jit(gen)(seed_key(seed))


# ---------------------------------------------------------------------------
# the matrix product, in the stated precision and in the one below it
# ---------------------------------------------------------------------------

def _fp8(x):
    """Round to float8_e4m3 with one scale per tensor, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


_ROUNDINGS = {"fp8": _fp8, "bf16": _bf16}


def make_dot(precision: str):
    """``dot(spec, a, b)``: an einsum in float32 at ``highest``; for a
    lower ``precision`` its operands and cotangents are rounded first."""
    def exact(spec, a, b):
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    if precision == "f32":
        return exact
    rnd = _ROUNDINGS[precision]

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def dot(spec, a, b):
        return exact(spec, rnd(a), rnd(b))

    def fwd(spec, a, b):
        ra, rb = rnd(a), rnd(b)
        return exact(spec, ra, rb), (ra, rb)

    def bwd(spec, res, g):
        _, vjp = jax.vjp(functools.partial(exact, spec), *res)
        return vjp(rnd(g))

    dot.defvjp(fwd, bwd)
    return dot


# ---------------------------------------------------------------------------
# optimizers, plain
# ---------------------------------------------------------------------------

def _adam(hp):
    lr, b1, b2 = hp["learning_rate"], hp.get("beta1", 0.9), \
        hp.get("beta2", 0.999)
    eps = hp.get("epsilon", 1e-8)

    def init(w):
        return (jnp.zeros_like(w), jnp.zeros_like(w))

    def update(w, g, state, t):
        m, v = state
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        step = lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return w - step, (m, v)
    return init, update


def _sgd(hp):
    lr, mom = hp["learning_rate"], hp.get("momentum", 0.0)

    def init(w):
        return (jnp.zeros_like(w),)

    def update(w, g, state, t):
        m = mom * state[0] - lr * g
        return w + m, (m,)
    return init, update


OPTIMIZERS = {"adam": _adam, "sgd": _sgd}


def first_gradient_from_state(name: str, hp: dict):
    """How the first gradient, as the optimizer got it, reads off the
    optimizer's state after one step: ``f(state leaves) -> gradient``."""
    if name == "adam":
        return lambda st: st[0] / (1 - hp.get("beta1", 0.9))
    if name == "sgd":
        if not hp.get("momentum"):
            raise ValueError("sgd without momentum keeps no state to read "
                             "the first gradient from")
        return lambda st: st[0] / -hp["learning_rate"]
    raise ValueError(f"no plain form of optimizer {name!r}")


# ---------------------------------------------------------------------------
# following the first steps
# ---------------------------------------------------------------------------

def _norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def follow(loss_sum, weights: dict, batches: list, optimizer: tuple,
           steps: int = 3, block_rows: int = None, rows: int = None) -> dict:
    """Train ``steps`` steps in float32. ``loss_sum(params, x, y)`` is the
    SUM of the per-row losses of a block of rows; the step's loss is the
    mean over the batch's rows and the gradient the optimizer gets is that
    mean's. → ``{"loss": [..], "grad1": {leaf: norm}, "change": {leaf:
    norm of w_after - w_before}}`` as Python floats."""
    name, hp = optimizer
    init, update = OPTIMIZERS[name](hp)
    grad_block = jax.jit(jax.value_and_grad(loss_sum))

    @jax.jit
    def apply(params, grads, state, t, n_rows):
        grads = {k: g / n_rows for k, g in grads.items()}
        new = {k: update(params[k], grads[k], state[k], t) for k in params}
        return ({k: v[0] for k, v in new.items()},
                {k: v[1] for k, v in new.items()}, _norms(grads))

    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    diff_norms = jax.jit(lambda a, b: _norms(
        {k: a[k] - b[k] for k in a}))

    params = dict(weights)
    state = {k: init(w) for k, w in params.items()}
    losses, grad1 = [], None
    for t, (x, y) in enumerate(batches[:steps], start=1):
        n = x.shape[0] if rows is None else rows
        blk = min(block_rows or n, n)
        if n % blk:
            raise ValueError(f"{n} rows do not divide into blocks of {blk}")
        total, grads = 0.0, None
        for r in range(0, n, blk):
            val, g = grad_block(params, x[r:r + blk], y[r:r + blk])
            total = total + val
            grads = g if grads is None else add(grads, g)
        params, state, gn = apply(params, grads, state, float(t), float(n))
        losses.append(total / n)
        if t == 1:
            grad1 = gn
    change = diff_norms(params, weights)
    return {"loss": [float(v) for v in losses],
            "grad1": {k: float(v) for k, v in grad1.items()},
            "change": {k: float(v) for k, v in change.items()}}


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def _worst_leaf(got: dict, ref: dict, leaves) -> tuple:
    """→ (the widest gap, its leaf): for each leaf the gap BETWEEN NORMS
    (not the norm of a difference), against the reference's norm of that
    leaf or of the median leaf, whichever is larger: some gradients are
    all but zero."""
    floor = statistics.median(ref[k] for k in leaves)
    gaps = {}
    for k in leaves:
        gap = abs(got[k] - ref[k]) / max(ref[k], floor, 1e-30)
        gaps[k] = gap if math.isfinite(gap) else math.inf
    at = max(gaps, key=gaps.get)
    return gaps[at], at


def readings(got: dict, ref: dict) -> dict:
    """``{name: (value, leaf or "")}``: each step's loss gap as a share of
    the reference's loss (``loss1``..), the worst leaf's gap of the first
    gradient's norm (``grad1``) and of the parameters' change over the
    steps followed (``change3`` after three). A cell's limits say which of
    them it compares."""
    out = {}
    for i, (a, b) in enumerate(zip(got["loss"], ref["loss"]), start=1):
        gap = abs(a - b) / abs(b)
        out[f"loss{i}"] = (gap if math.isfinite(gap) else math.inf, "")
    leaves = sorted(ref["grad1"])
    out["grad1"] = _worst_leaf(got["grad1"], ref["grad1"], leaves)
    floor = statistics.median(ref["grad1"].values()) * DEAD_GRADIENT_SHARE
    alive = [k for k in leaves if ref["grad1"][k] >= floor]
    out[f"change{len(ref['loss'])}"] = _worst_leaf(
        got["change"], ref["change"], alive)
    return out


def compare(got: dict, ref: dict, limits: dict) -> tuple:
    """→ ``(correct, {name: {"value", "limit", "leaf"}})``. Every number in
    ``limits`` is compared; one above its limit, or not finite, makes the
    run not correct."""
    read = readings(got, ref)
    compared, ok = {}, True
    for name, limit in limits.items():
        value, leaf = read[name]
        if not math.isfinite(value):
            value, ok = 1e30, False
        elif value > limit:
            ok = False
        compared[name] = {"value": value, "limit": limit}
        if leaf:
            compared[name]["leaf"] = leaf
    return ok, compared
