"""Plain reference of the dense decoder the model cells run, and of one
Fed-PLT round over it, in float32 at the highest matmul precision.

The layer follows the configuration file (``bench/configs``) and the
departures from the published model that the file lists under
``departures`` (RoPE on the whole head, the embedding scaled by
sqrt(hidden_size), norm weights stored as an offset from 1): those are
what the program computes, and the reference computes the same.

Weights live in one tree whose layout is the program's parameter tree
(``stages[0]["0"]`` holds each layer's weights stacked on a leading
layer axis; ``mlp.wi`` holds the gate and the up projection side by
side).  :func:`init_params` makes them from a key in one jitted call;
the model cells hand the same function to the program as its ``init``,
so both sides start from the same numbers without sharing code.

The round is Algorithm 1 of the paper with h = 0:

    y = mean_i z_i;  v_i = 2 y - z_i;
    w_i = N_e steps of  w <- w - gamma (grad f_i(w) + (w - v_i) / rho)
          from x_i;
    z_i <- z_i + 2 damping (w_i - y);  x_i <- w_i

``precision="bf16"`` is the control, one precision step below the
configuration's float32: every weight matmul (forward and backward)
takes operands rounded to bfloat16, and the state (weights, the
coordinator point, the reflections) is stored in bfloat16.  The rounding
is ``lax.reduce_precision``, which the compiler keeps (a round trip
through a narrower dtype may be folded away as excess precision).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims(model: dict) -> dict:
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    return dict(d=d, h=h, hkv=model["num_key_value_heads"],
                hd=model.get("head_dim", d // h),
                f=model["intermediate_size"], v=model["vocab_size"],
                layers=model["num_hidden_layers"],
                eps=model["rms_norm_eps"], theta=model["rope_theta"])


def param_shapes(model: dict) -> dict:
    k = dims(model)
    d, L, hd = k["d"], k["layers"], k["hd"]
    layer = {
        "ln1": (L, d),
        "attn": {"wq": (L, d, k["h"] * hd), "wk": (L, d, k["hkv"] * hd),
                 "wv": (L, d, k["hkv"] * hd), "wo": (L, k["h"] * hd, d)},
        "ln2": (L, d),
        "mlp": {"wi": (L, d, 2 * k["f"]), "wo": (L, k["f"], d)},
    }
    return {"stages": [{"0": layer}], "embed": (k["v"], d),
            "final_norm": (d,)}


def _is_shape(x):
    return isinstance(x, tuple)


MODEL_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "intermediate_size", "vocab_size",
              "num_hidden_layers", "rms_norm_eps", "rope_theta")


def model_key(model: dict) -> tuple:
    """The sizes the reference reads, hashable (a static jit argument)."""
    return tuple((k, model[k]) for k in MODEL_KEYS if k in model)


def init_params(key, model: dict, dtype):
    """Seeded weights: matrices N(0, 1/fan_in), the embedding
    N(0, 1/hidden), norm offsets N(0, 0.05^2), rounded to ``dtype``."""
    shapes = param_shapes(model)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)

    @jax.jit
    def make(key):
        out = []
        for i, (path, shape) in enumerate(paths):
            name = jax.tree_util.keystr(path)
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if "ln" in name or "norm" in name:
                x = 0.05 * x
            elif "embed" in name:
                x = x * model["hidden_size"] ** -0.5
            else:
                x = x * shape[-2] ** -0.5
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make(key)


# ---------------------------------------------------------------------------
# matmuls and storage: float32 at HIGHEST, or the bfloat16 control
# ---------------------------------------------------------------------------

def _bf16(a, axis=None):
    del axis
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


ROUND = {"bf16": _bf16}
# the control of a configuration: one precision step below its dtype
CONTROL = {"float32": "bf16"}


def _mm_f32(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _lowered_matmul(rnd):
    """``a (..., K) @ b (K, N)`` from operands rounded by ``rnd`` along
    the contracted axis, the backward products likewise, accumulated in
    float32."""

    @jax.custom_vjp
    def mm(a, b):
        return _mm_f32(rnd(a, 1), rnd(b, 0))

    def fwd(a, b):
        return mm(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        return (_mm_f32(rnd(g, 1), rnd(b.T, 0)),
                _mm_f32(rnd(a.T, 1), rnd(g, 0)))

    mm.defvjp(fwd, bwd)

    def nd(a, b):
        lead = a.shape[:-1]
        out = mm(a.reshape(-1, a.shape[-1]), b)
        return out.reshape(lead + (b.shape[-1],))

    return nd


MATMULS = {"f32": _mm_f32, **{k: _lowered_matmul(r) for k, r in ROUND.items()}}
STORES = {"f32": lambda a: a, **ROUND}


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x (B, S, H, D): rotate the two halves of each head by position."""
    S, D = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal softmax attention; query head j reads key head j // G."""
    B, S, H, D = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / math.sqrt(D)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    return o.reshape(B, S, H * D)


def loss(params, tokens, labels, model: dict, precision: str = "f32"):
    """Mean next-token cross-entropy of one agent's batch (B, S)."""
    k = dims(model)
    mm = MATMULS[precision]
    B, S = tokens.shape
    x = params["embed"][tokens] * math.sqrt(k["d"])
    layers = params["stages"][0]["0"]
    for li in range(k["layers"]):
        p = jax.tree_util.tree_map(lambda a, li=li: a[li], layers)
        h = _rms_norm(x, p["ln1"], k["eps"])
        q = mm(h, p["attn"]["wq"]).reshape(B, S, k["h"], k["hd"])
        kk = mm(h, p["attn"]["wk"]).reshape(B, S, k["hkv"], k["hd"])
        vv = mm(h, p["attn"]["wv"]).reshape(B, S, k["hkv"], k["hd"])
        o = _attention(_rope(q, k["theta"]), _rope(kk, k["theta"]), vv)
        x = x + mm(o, p["attn"]["wo"])
        h = _rms_norm(x, p["ln2"], k["eps"])
        gate, up = jnp.split(mm(h, p["mlp"]["wi"]), 2, axis=-1)
        x = x + mm(jax.nn.silu(gate) * up, p["mlp"]["wo"])
    x = _rms_norm(x, params["final_norm"], k["eps"])
    logits = mm(x, params["embed"].T)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


# ---------------------------------------------------------------------------
# one Fed-PLT round
# ---------------------------------------------------------------------------

def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


@functools.partial(jax.jit, static_argnames=("model", "n_epochs", "gamma",
                                             "rho", "precision"),
                   donate_argnums=(0,))
def local_solve(w, v, tokens, labels, *, model, n_epochs, gamma, rho,
                precision):
    """``n_epochs`` gradient steps on f_i(w) + ||w - v||^2 / (2 rho) from
    ``w``; returns ``(w, loss at the last epoch's starting point)``."""
    model = dict(model)
    grad = jax.value_and_grad(loss)
    store = STORES[precision]

    def body(w, _):
        val, g = grad(w, tokens, labels, model, precision)
        w = _tmap(lambda wl, gl, vl: store(wl - gamma * (gl + (wl - vl)
                                                         / rho)), w, g, v)
        return w, val

    w, vals = jax.lax.scan(body, w, None, length=n_epochs)
    return w, vals[-1]


@functools.partial(jax.jit, static_argnames=("precision",))
def _mean(trees, *, precision):
    return _tmap(lambda *ls: STORES[precision](sum(ls) / len(ls)), *trees)


@functools.partial(jax.jit, static_argnames=("precision",))
def _reflect(y, z, *, precision):
    return _tmap(lambda a, b: STORES[precision](2.0 * a - b), y, z)


@functools.partial(jax.jit, static_argnames=("damping", "precision"),
                   donate_argnums=(0,))
def _z_update(z, w, y, *, damping, precision):
    return _tmap(lambda zl, wl, yl: STORES[precision](
        zl + 2.0 * damping * (wl - yl)), z, w, y)


@functools.partial(jax.jit, static_argnames=("precision",))
def _store(tree, *, precision):
    return _tmap(STORES[precision], tree)


def run_rounds(theta0, batches, model: dict, fed: dict, precision="f32",
               on_round=None):
    """Rounds of Fed-PLT from every agent at ``theta0`` (float32), one
    per batch of ``batches`` (leaves ``(N, B, S)``).  ``on_round(r, xs,
    zs)`` sees the agents' states after round ``r`` (1-based).  Returns
    the rounds' losses: the mean over agents of the last epoch's loss."""
    n = fed["n_agents"]
    key = model_key(model)
    start = _store(theta0, precision=precision)
    xs = [_tmap(jnp.copy, start) for _ in range(n)]
    zs = [_tmap(jnp.copy, start) for _ in range(n)]
    del start
    losses = []
    for r, batch in enumerate(batches, start=1):
        y = _mean(zs, precision=precision)
        vals = []
        for i in range(n):
            v = _reflect(y, zs[i], precision=precision)
            xs[i], val = local_solve(
                xs[i], v, batch["tokens"][i], batch["labels"][i],
                model=key, n_epochs=fed["n_epochs"], gamma=fed["gamma"],
                rho=fed["rho"], precision=precision)
            del v
            zs[i] = _z_update(zs[i], xs[i], y, damping=fed["damping"],
                              precision=precision)
            vals.append(val)
        del y
        losses.append(float(sum(float(v) for v in vals) / n))
        if on_round is not None:
            on_round(r, xs, zs)
    return losses
