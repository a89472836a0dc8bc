"""Shardings for auxiliary trees: optimizer states (mirroring param rules,
incl. Adafactor's factored moments) and decode caches (sequence-sharded over
the model axis — split-KV decode, DESIGN.md §5)."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .sharding import AXIS_BATCH, AXIS_MODEL

# ---------------------------------------------------------------------------
# sharding of aux trees (optimizer state, caches)
# ---------------------------------------------------------------------------

def _axes_ok(mesh, dim, entry):
    if entry is None:
        return True
    names = entry if isinstance(entry, tuple) else (entry,)
    tot = int(np.prod([dict(zip(mesh.axis_names,
                                mesh.devices.shape)).get(a, 1)
                       for a in names]))
    return dim % tot == 0


def _filt(mesh, items, shape):
    out = []
    for i, e in enumerate(items[:len(shape)]):
        if e is not None and isinstance(e, tuple):
            e = tuple(a for a in e if a in mesh.axis_names) or None
        elif e is not None and e not in mesh.axis_names:
            e = None
        out.append(e if e is not None and _axes_ok(mesh, shape[i], e)
                   else None)
    out += [None] * (len(shape) - len(out))
    return NamedSharding(mesh, P(*out))


def opt_state_specs(state_abs, params_sh, mesh):
    """Shardings for a train state: params per rules; m/v mirror params;
    adafactor factored vr/vc inherit the matching params dims; scalars
    replicated."""
    rep = NamedSharding(mesh, P())

    def like_params(tree):
        flat_p, treedef = jax.tree_util.tree_flatten(params_sh)
        flat_t = treedef.flatten_up_to(tree)
        return jax.tree_util.tree_unflatten(treedef, [
            p for p, _ in zip(flat_p, flat_t)])

    out = {"step": rep, "params": params_sh}
    opt = state_abs["opt"]
    if "m" in opt:                       # adamw
        out["opt"] = {"m": like_params(opt["m"]),
                      "v": like_params(opt["v"]), "t": rep}
    elif "f" in opt:                     # adafactor
        p_leaves = jax.tree_util.tree_leaves(params_sh)
        fs = []
        for sh, st in zip(p_leaves, opt["f"]):
            spec = list(sh.spec) + [None] * 8
            if "vr" in st:
                fs.append({"vr": NamedSharding(
                    mesh, P(*spec[:len(st["vr"].shape)])),
                    "vc": NamedSharding(mesh, P(*(
                        spec[:len(st["vc"].shape) - 1]
                        + [spec[len(st["vr"].shape)]])))})
            else:
                fs.append({"v": sh})
        out["opt"] = {"f": tuple(fs), "t": rep}
    else:                                # sgd
        out["opt"] = {"m": like_params(opt["m"])}
    if "err" in state_abs:
        out["err"] = like_params(state_abs["err"])
    return out


_CACHE_RULES = {
    "k":    (None, AXIS_BATCH, AXIS_MODEL, None, None),
    "v":    (None, AXIS_BATCH, AXIS_MODEL, None, None),
    "ck":   (None, AXIS_BATCH, AXIS_MODEL, None, None),
    "cv":   (None, AXIS_BATCH, AXIS_MODEL, None, None),
    "ckv":  (None, AXIS_BATCH, AXIS_MODEL, None),
    "kr":   (None, AXIS_BATCH, AXIS_MODEL, None),
    "h":    (None, AXIS_BATCH, AXIS_MODEL, None),
    "conv": (None, AXIS_BATCH, None, AXIS_MODEL),
    "C":    (None, AXIS_BATCH, None, None, AXIS_MODEL),
    "n":    (None, AXIS_BATCH, None, None),
    "m":    (None, AXIS_BATCH, None),
    "c":    (None, AXIS_BATCH, AXIS_MODEL),
    "pos":  (),
    # paged KV pools (L, n_pages, page_size, n_kv·hd), lane-dense: the
    # merged head axis over model, in whole kv heads only — cache_specs
    # replicates them where n_kv does not divide the axis (the paged
    # kernel runs shard-local only where it does)
    "pool_k": (None, None, None, AXIS_MODEL),
    "pool_v": (None, None, None, AXIS_MODEL),
    # quantized-pool scale rows (L, n_pages, n_kv·page_size), head-major:
    # sharded in whole heads like the pools (DESIGN.md §11)
    "scale_k": (None, None, AXIS_MODEL),
    "scale_v": (None, None, AXIS_MODEL),
}


_HEAD_MAJOR = ("pool_k", "pool_v", "scale_k", "scale_v")


def pool_head_axis(kv_heads, mesh):
    """Mesh axis of the paged pools' merged head axis: the model axis
    where ``kv_heads`` divides it (whole heads per shard), else None —
    a split inside a head would make every layer gather its pool."""
    if mesh is None or kv_heads is None or AXIS_MODEL not in mesh.axis_names:
        return None
    return AXIS_MODEL if kv_heads % mesh.shape[AXIS_MODEL] == 0 else None


def cache_specs(cache_abs, mesh, kv_heads=None):
    """Shardings for a decode cache.  The paged pools' last axis merges
    ``kv_heads`` heads, so it is split over the model axis only in whole
    heads (``pool_head_axis``); otherwise, or with ``kv_heads`` unknown,
    the pools stay replicated."""
    whole_heads = pool_head_axis(kv_heads, mesh) is not None

    def one(path, leaf):
        name = None
        for pp in reversed(path):
            if hasattr(pp, "key"):
                name = str(pp.key)
                break
        if name in _HEAD_MAJOR and not whole_heads:
            return _filt(mesh, [], leaf.shape)
        items = _CACHE_RULES.get(name, ())
        return _filt(mesh, list(items), leaf.shape)
    return jax.tree_util.tree_map_with_path(one, cache_abs)


