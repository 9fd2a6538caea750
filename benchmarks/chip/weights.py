"""Seeded random weights for a GQA configuration with a SwiGLU MLP or
expert layers.

The benchmark makes the weights, not the program: the program under test
takes them as its checkpoint, and the plain reference reads the same
weights, so the reference never sees anything the program made.  The tree
has the program's checkpoint layout (``repro.models.transformer``): a
quantized projection is ``{"w", "sw", "sa"}`` (weight, LSQ weight step,
LSQ activation step); the repeated blocks are stacked on a leading layer
axis under ``pat/p0``.

Every block projection holds the same multiset of values for every seed:
the evenly spaced quantiles of N(0, 1/d_in), placed by a seeded
permutation.  EAGL scores a layer by the entropy of its quantized weight
histogram, which a permutation leaves unchanged, so the knapsack picks the
same mixed policy for every seed and every run serves the same compiled
programs.  The embedding (and an untied head) are pinned to 8 bits and
never selected, so they are plain Gaussians.

A dense configuration on one device is made whole by one jitted call on
the device, in the served dtype (``_make``).  ``Source`` makes the
weights a piece at a time, as a function of (seed, layer, leaf): one
block's tree on demand, and the embedding, final norm and head apart.  A
layer's projection ``p`` is the quantile normal of
``split(k_p, L)[layer]``, the key ``_make`` gives that layer of its
stack, so a dense layer's weights equal the stacked tree's; its LSQ steps
are summed over the layer alone, which on a TPU can differ from the
stack's in the last bits (PERF.md), so the two cells' configurations keep
``_make``.  A configuration with expert layers (``num_local_experts``) is
made only by ``Source``: each expert of a bank (gate, up: (E, d, f);
down: (E, f, d)) is the quantile normal of
``split(split(k_bank, L)[layer], E)[expert]`` with its own 4-bit step,
made one expert after another, and the router (d, E) is one more
projection, its steps at 8 bits as ``mlp.moe_apply`` serves it.  Set-up
and the check then hold one layer's weights at a time.

Given a mesh (a configuration's ``"mesh": {"model": n}``),
``Source.stacked`` places each leaf as the tensor-parallel engine shards
it: ``wq/wk/wv/gate/up`` on their output axis, ``wo/down`` on their input
axis, the embedding and head on their vocabulary axis, the rest
replicated.  Each layer is made whole on every device, its steps summed
over it as an input as on one device, and only the device's slice is
written into the stack; a step is a float sum whose order, on a TPU,
follows the shape it runs over and what it is computed beside.  A device
holds its slice of the tree, one layer, and while the edges are made the
embedding and head whole.
"""
from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

ACT_STEP_4BIT = 2.0 / math.sqrt(2.0 ** 3 - 1)   # LSQ init for unit-variance input


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any integer seed (wider than 32 bits included)."""
    s = seed % 2**64
    return jax.random.fold_in(jax.random.PRNGKey(s % 2**32), s // 2**32)


def _lsq_step(w: jax.Array, bits: float) -> jax.Array:
    """LSQ step-size init, 2 mean|w| / sqrt(qmax) (Esser et al., 2020)."""
    qmax = 2.0 ** (bits - 1) - 1.0
    return 2.0 * jnp.mean(jnp.abs(w.astype(jnp.float32))) / math.sqrt(qmax)


def _mix(x: jax.Array, consts: jax.Array, k: int) -> jax.Array:
    """A bijection of the k-bit integers: rounds of odd multiply, add and
    xor-shift, each invertible mod 2**k."""
    mask = jnp.uint32(2**k - 1) if k < 32 else jnp.uint32(0xFFFFFFFF)
    for r in range(consts.shape[0]):
        mul = consts[r, 0] | jnp.uint32(1)
        x = (x * mul + consts[r, 1]) & mask
        x = x ^ (x >> max(1, (k + r) // 2 - r % 2))
    return x


def permutation_index(key: jax.Array, n: int) -> jax.Array:
    """A seeded permutation of range(n) as uint32, elementwise (no sort):
    a bijection of the next power of two, cycle-walked back into range."""
    k = max(1, math.ceil(math.log2(n)))
    consts = jax.random.bits(key, (4, 2), jnp.uint32)
    x = _mix(jnp.arange(n, dtype=jnp.uint32), consts, k)
    if n == 2**k:
        return x
    return jax.lax.while_loop(lambda y: jnp.any(y >= n),
                              lambda y: jnp.where(y >= n,
                                                  _mix(y, consts, k), y), x)


def quantile_normal(key: jax.Array, shape, std: float, dtype) -> jax.Array:
    """N(0, std^2) quantiles at (i + 1/2)/n in a seeded order."""
    n = math.prod(shape)
    i = permutation_index(key, n)
    near = jnp.minimum(i, jnp.uint32(n - 1) - i).astype(jnp.float32)
    z = jax.scipy.special.ndtri((near + 0.5) / n)     # <= 0, exact tails
    z = jnp.where(2 * i.astype(jnp.float32) >= n - 1, -z, z)
    return (z * std).reshape(shape).astype(dtype)


def _shard(mesh, x, *spec):
    """``x`` placed on ``mesh`` by ``spec`` (replicated when it is empty);
    as it is without a mesh."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def _qdense(key, n_layers: int, d_in: int, d_out: int, dtype) -> dict:
    """Stacked projections."""
    keys = jax.random.split(key, n_layers)
    w = jax.vmap(lambda k: quantile_normal(
        k, (d_in, d_out), d_in ** -0.5, dtype))(keys)
    return {"w": w,
            "sw": jax.vmap(lambda x: _lsq_step(x, 4.0))(w),
            "sa": jnp.full((n_layers,), ACT_STEP_4BIT, jnp.float32)}


def _norm(key, cfg: dict, shape):
    if cfg["norm"] == "nonparam_ln":
        return {}
    if cfg["norm"] in ("rms", "ln"):
        out = {"scale": (1.0 + 0.1 * jax.random.normal(key, shape)
                         ).astype(cfg["dtype"])}
        if cfg["norm"] == "ln":          # the program's layout; dbrx has none
            out["bias"] = jnp.zeros(shape, cfg["dtype"])
        return out
    raise ValueError(f"unknown norm {cfg['norm']!r}")


COLUMN = (None, "model")   # a layer's output axis; the head's vocabulary
ROW = ("model", None)      # a layer's input axis; the embedding's vocabulary


@functools.partial(jax.jit, static_argnums=(0,))
def _make(frozen: tuple, key: jax.Array) -> dict:
    cfg = dict(frozen)
    dt = jnp.dtype(cfg["dtype"])
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    f, v = cfg["intermediate_size"], cfg["vocab_size"]
    ks = iter(jax.random.split(key, 16))
    embed = (jax.random.normal(next(ks), (v, d), jnp.float32)
             * 0.02).astype(dt)

    def qdense(d_in, d_out):
        return _qdense(next(ks), L, d_in, d_out, dt)

    block = {
        "norm1": _norm(next(ks), cfg, (L, d)),
        "attn": {"wq": qdense(d, hq * hd),
                 "wk": qdense(d, hkv * hd),
                 "wv": qdense(d, hkv * hd),
                 "wo": qdense(hq * hd, d)},
        "norm2": _norm(next(ks), cfg, (L, d)),
        "mlp": {"gate": qdense(d, f),
                "up": qdense(d, f),
                "down": qdense(f, d)},
    }
    params = {"embed": {"w": embed, "sw": _lsq_step(embed, 8.0)},
              "pat": {"p0": block},
              "final_norm": _norm(next(ks), cfg, (d,))}
    if not cfg["tie_word_embeddings"]:
        head = (jax.random.normal(next(ks), (d, v), jnp.float32)
                * d ** -0.5).astype(dt)
        params["head"] = {"w": head,
                          "sw": _lsq_step(head, 8.0),
                          "sa": jnp.float32(cfg["head_act_step"])}
    return params


SHAPE_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "intermediate_size",
              "vocab_size", "norm", "tie_word_embeddings", "head_act_step",
              "dtype")
EXPERT_KEYS = ("num_local_experts", "num_experts_per_tok",
               "moe_intermediate_size")
# The order ``_make`` draws its keys in, then the router's.
KEY_ORDER = ("embed", "norm1", "wq", "wk", "wv", "wo", "norm2", "gate", "up",
             "down", "final_norm", "head", "router")
ACT_STEP_8BIT = 2.0 / math.sqrt(2.0 ** 7 - 1)
# A layer's projections and their place on a mesh: the layer axis first.
SPECS = {"wq": COLUMN, "wk": COLUMN, "wv": COLUMN, "gate": COLUMN,
         "up": COLUMN, "wo": ROW, "down": ROW}


def has_experts(cfg: dict) -> bool:
    return "num_local_experts" in cfg


def _frozen(cfg: dict) -> tuple:
    return tuple((k, cfg[k]) for k in SHAPE_KEYS + EXPERT_KEYS if k in cfg)


def make(cfg: dict, seed: int, mesh=None) -> dict:
    """The configuration's whole tree for ``seed``.  A dense configuration
    on one device comes from the stacked ``_make``; on a mesh, or with
    expert layers, each layer is made by ``Source`` and stacked."""
    if mesh is None and not has_experts(cfg):
        frozen = tuple((k, cfg[k]) for k in SHAPE_KEYS)
        return jax.block_until_ready(_make(frozen, key_for(seed)))
    return Source(cfg, seed, mesh).stacked()


def for_reference(cfg: dict, seed: int, mesh=None):
    """What the plain reference reads: the whole tree, or, with expert
    layers, a ``Source`` it takes one layer at a time."""
    return Source(cfg, seed) if has_experts(cfg) else make(cfg, seed, mesh)


def _projections(cfg: dict):
    """(group, name, d_in, d_out) of a layer's dense projections, and
    (name, d_in, d_out) of its expert banks."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    dense = [("attn", "wq", d, q), ("attn", "wk", d, kv),
             ("attn", "wv", d, kv), ("attn", "wo", q, d)]
    if not has_experts(cfg):
        f = cfg["intermediate_size"]
        return dense + [("mlp", "gate", d, f), ("mlp", "up", d, f),
                        ("mlp", "down", f, d)], []
    f = cfg["moe_intermediate_size"]
    return (dense + [("moe", "router", d, cfg["num_local_experts"])],
            [("gate", d, f), ("up", d, f), ("down", f, d)])


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_weights(frozen: tuple, key: jax.Array, i, mesh=None) -> dict:
    """Layer ``i``'s weights and norm parameters, without LSQ steps: whole,
    and on a mesh whole on every device."""
    cfg = dict(frozen)
    dt = jnp.dtype(cfg["dtype"])
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    ks = jax.random.split(key, 16)

    def at(name):                  # the key _make gives layer i of a stack
        return jax.random.split(ks[KEY_ORDER.index(name)], L)[i]

    def normal(k, d_in, d_out):
        return quantile_normal(k, (d_in, d_out), d_in ** -0.5, dt)

    out = {norm: {k: v[i] for k, v in _norm(ks[KEY_ORDER.index(norm)], cfg,
                                            (L, d)).items()}
           for norm in ("norm1", "norm2")}
    dense, banks = _projections(cfg)
    for group, name, d_in, d_out in dense:
        out.setdefault(group, {})[name] = {"w": normal(at(name), d_in, d_out)}
    for name, d_in, d_out in banks:       # one expert after another
        experts = jax.random.split(at(name), cfg["num_local_experts"])
        out["moe"][name] = {"w": jax.lax.map(
            lambda k, a=d_in, b=d_out: normal(k, a, b), experts)}
    return jax.tree.map(lambda x: _shard(mesh, x), out)


@functools.partial(jax.jit, static_argnums=1)
def _step(w: jax.Array, bits: float) -> jax.Array:
    """The LSQ step of a projection, or of each expert of a bank, summed
    over the weights as an input: the same program alone or on a mesh."""
    if w.ndim == 3:
        return jax.lax.map(lambda x: _lsq_step(x, bits), w)
    return _lsq_step(w, bits)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _edges(frozen: tuple, key: jax.Array, mesh=None) -> dict:
    """The embedding, final norm and head as ``_make`` makes them; on a mesh
    whole on every device, each keeping its slice."""
    cfg = dict(frozen)
    dt = jnp.dtype(cfg["dtype"])
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    ks = jax.random.split(key, 16)
    embed = _shard(mesh, (jax.random.normal(ks[0], (v, d), jnp.float32)
                          * 0.02).astype(dt))
    out = {"embed": {"w": _shard(mesh, embed, *ROW),
                     "sw": _lsq_step(embed, 8.0)},
           "final_norm": _norm(ks[KEY_ORDER.index("final_norm")], cfg, (d,))}
    if not cfg["tie_word_embeddings"]:
        head = _shard(mesh, (jax.random.normal(
            ks[KEY_ORDER.index("head")], (d, v), jnp.float32)
            * d ** -0.5).astype(dt))
        out["head"] = {"w": _shard(mesh, head, *COLUMN),
                       "sw": _lsq_step(head, 8.0),
                       "sa": jnp.float32(cfg["head_act_step"])}
    return out


@functools.partial(jax.jit, donate_argnums=0)
def put(stack, layer, i):
    """``layer`` written in place into position ``i`` of ``stack``, a tree
    of the same structure with a leading layer axis."""
    return jax.tree.map(lambda s, x: s.at[i].set(x), stack, layer)


class Source:
    """The weights of ``cfg`` for ``seed`` as a function of (layer, leaf):
    ``layer(i)`` makes one block's tree (the program's layout, unstacked)
    and holds it until another is asked for; ``edges()`` the embedding,
    final norm and head.  ``seconds`` sums the time spent making."""

    def __init__(self, cfg: dict, seed: int, mesh=None):
        self.frozen = _frozen(cfg)
        self.key = key_for(seed)
        self.mesh = mesh
        self.n_layers = cfg["num_hidden_layers"]
        dense, banks = _projections(cfg)
        e = cfg.get("num_local_experts", 0)
        self._quantized = (
            [((g, n), 8.0 if n == "router" else 4.0,
              ACT_STEP_8BIT if n == "router" else ACT_STEP_4BIT, ())
             for g, n, _, _ in dense]
            + [(("moe", n), 4.0, ACT_STEP_4BIT, (e,)) for n, _, _ in banks])
        self.seconds = 0.0
        self._held = None

    def _timed(self, make):
        t = time.perf_counter()
        out = jax.block_until_ready(make())
        self.seconds += time.perf_counter() - t
        return out

    def layer(self, i: int) -> dict:
        if self._held is not None and self._held[0] == i:
            return self._held[1]
        self._held = None                 # never two layers at once

        def make():
            tree = _layer_weights(self.frozen, self.key, i, self.mesh)
            for (group, name), bits, sa, shape in self._quantized:
                node = tree[group][name]
                node["sw"] = _step(node["w"], bits)
                node["sa"] = jnp.full(shape, sa, jnp.float32)
            return tree

        tree = self._timed(make)
        self._held = (i, tree)
        return tree

    def release(self) -> None:
        self._held = None

    def edges(self) -> dict:
        return self._timed(lambda: _edges(self.frozen, self.key, self.mesh))

    def fetch(self, unit, path: tuple):
        """A policy unit's tensor and LSQ step (as
        ``transformer.fetch_unit_tensor`` reads them from a whole tree),
        from its layer."""
        if path[:2] != ("pat", "p0"):
            raise KeyError(f"{path}: only a block's tensors are fetched")
        node = self.layer(unit.layer)
        for name in path[2:-1]:
            node = node[name]
        w, step = node[path[-1]], node["sw"]
        if unit.sub is not None:
            w, step = w[unit.sub], step[unit.sub]
        return w, step

    def stacked(self) -> dict:
        """The whole tree, each layer made by ``layer`` and written into its
        place in the stack; on a mesh each device keeps its slice."""
        out = self.edges()
        stack = None
        for i in range(self.n_layers):
            layer = self.layer(i)
            self.release()
            if stack is None:
                stack = jax.tree_util.tree_map_with_path(self._zeros, layer)
            stack = self._timed(functools.partial(put, stack, layer, i))
            del layer
        out["pat"] = {"p0": stack}
        return out

    def _zeros(self, path, x):
        """A leaf's stack, placed as the tensor-parallel engine shards it."""
        shape = (self.n_layers,) + x.shape
        names = [getattr(p, "key", None) for p in path]
        spec = ((None,) + SPECS[names[-2]]
                if names[-1] == "w" and names[-2] in SPECS else ())
        if self.mesh is None:
            return jnp.zeros(shape, x.dtype)
        return jax.jit(lambda: jnp.zeros(shape, x.dtype),
                       out_shardings=NamedSharding(self.mesh, P(*spec)))()
