"""Weights drawn from the run's seed, on the device, by name.

Every leaf of a reference's `layout()` gets its own key, folded from the
seed's key by the CRC of its path, and a layer-stacked leaf draws each layer
from that key folded with the layer index.  So the program's weights (all
leaves, in one jitted call, straight into their shardings) and the
reference's (one layer at a time, after the window) are the same numbers,
and neither side hands the other anything.  The seed enters as an argument,
so one compiled program serves every seed."""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(seed)


def _draw(key, shape, kind, scale):
    z = jax.random.normal(key, shape, jnp.float32)
    return 1.0 + scale * z if kind == "norm" else scale * z


def _leaf(base, path, spec, layer=None):
    shape, kind, scale = spec
    key = jax.random.fold_in(base, zlib.crc32("/".join(path).encode()))
    if path[0] != "seg0":
        return _draw(key, shape, kind, scale)
    if layer is not None:
        return _draw(jax.random.fold_in(key, layer), shape[1:], kind, scale)
    return jnp.stack([_draw(jax.random.fold_in(key, i), shape[1:], kind,
                            scale) for i in range(shape[0])])


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf
    return out


def abstract(layout: dict) -> dict:
    """The nested tree of ShapeDtypeStructs the layout describes."""
    return _nest({p: jax.ShapeDtypeStruct(s[0], jnp.float32)
                  for p, s in layout.items()})


def maker(layout: dict, shardings=None):
    """The compiled fn(key) -> all weights (placed by ``shardings``, a tree
    of the same structure, when given)."""
    def build(base):
        return _nest({p: _leaf(base, p, s) for p, s in layout.items()})

    return jax.jit(build, out_shardings=shardings)


def make(layout: dict, key, shardings=None) -> dict:
    """All weights, in one jitted call."""
    return maker(layout, shardings)(key)


def layer_maker(layout: dict, sharding=None):
    """A compiled fn(key, layer) -> layer ``layer``'s slice of every
    layer-stacked leaf, as a nested tree without the layer axis, drawn
    straight into ``sharding`` when given."""
    def build(base, layer):
        return _nest({p: _leaf(base, p, s, layer)
                      for p, s in layout.items() if p[0] == "seg0"})

    return jax.jit(build, out_shardings=sharding)


def make_rest(layout: dict, key, sharding=None) -> dict:
    """Every leaf that is not layer-stacked (embedding, norms, head),
    drawn straight into ``sharding`` when given."""
    def build(base):
        return _nest({p: _leaf(base, p, s)
                      for p, s in layout.items() if p[0] != "seg0"})

    return jax.jit(build, out_shardings=sharding)(key)
