"""The program's model configuration for a cell, and the weights the benchmark
makes from the seed.

A configuration file (``bench/configs/<name>.json``) names a registry
architecture (``base``), the fields replaced on it (``replace``), and under
``model`` every number the reference needs.  The program's ``ModelConfig`` is
``dataclasses.replace(get_config(base), **replace)``, and it must agree with the
``model`` block, so the reference and the program run the same model.

The weights are the benchmark's: one jitted call makes them on the device from
the seed, in the configuration's parameter dtype, in the reference's layout
(see ``bench/reference.py``).  ``program_tree`` lays the same arrays out as the
program's parameter tree, checked leaf by leaf against the program's own
initialiser's shapes and dtypes.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

PROGRAM_PATHS = {  # reference leaf -> path inside the program's layer dict
    "norm1": ("norm1",),
    "norm2": ("norm2",),
    "wq": ("mixer", "wq"),
    "wk": ("mixer", "wk"),
    "wv": ("mixer", "wv"),
    "wo": ("mixer", "wo"),
    "w_gate": ("ffn", "w_gate"),
    "w_up": ("ffn", "w_up"),
    "w_down": ("ffn", "w_down"),
}


def program_config(cfile: dict):
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(cfile["base"]), **cfile.get("replace", {}))
    for key, want in cfile["model"].items():
        got = getattr(cfg, key)
        if got != want:
            raise SystemExit(f"bench: configuration {cfile['name']}: the program has {key}={got!r}, the file states {want!r}")
    return cfg


def leaf_shapes(m: dict) -> dict:
    L, d, H, Hkv, Dh, F, V = (m[k] for k in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff", "vocab_size"))
    shapes = {
        "embed": (V, d),
        "final_norm": (d,),
        "layers": {
            "norm1": (L, d),
            "norm2": (L, d),
            "wq": (L, d, H * Dh),
            "wk": (L, d, Hkv * Dh),
            "wv": (L, d, Hkv * Dh),
            "wo": (L, H * Dh, d),
            "w_gate": (L, d, F),
            "w_up": (L, d, F),
            "w_down": (L, F, d),
        },
    }
    if not m["tie_embeddings"]:
        shapes["lm_head"] = (d, V)
    return shapes


def _std(name: str, shape, m: dict) -> float:
    fan_in = shape[-2] if len(shape) >= 2 else 1
    if name == "embed":
        return m["d_model"] ** -0.5
    if name in ("wo", "w_down"):  # residual projections, scaled by depth
        return (fan_in * 2 * m["n_layers"]) ** -0.5
    return fan_in**-0.5


def make_weights(m: dict, key):
    """All weights from ``key`` in one jitted call, in the parameter dtype."""
    dt = jnp.dtype(m["param_dtype"])
    shapes = leaf_shapes(m)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        out = []
        for i, (path, shape) in enumerate(flat):
            name = path[-1].key
            k = jax.random.fold_in(key, i)
            if "norm" in name:  # gains g of (1 + g): small, so that they matter
                x = 0.1 * jax.random.normal(k, shape, jnp.float32)
            else:
                x = jax.random.truncated_normal(k, -3.0, 3.0, shape, jnp.float32) * _std(name, shape, m)
            out.append(x.astype(dt))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(key)


def seed_key(seed: int, stream: int):
    """A PRNG key for ``stream`` of ``seed`` (any size of whole number)."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32), impl="threefry2x32")


def small_seed(seed: int, stream: int) -> int:
    """A seed under 2**31 for ``stream`` of ``seed``, for code that takes an int."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0] & 0x7FFFFFFF)


def program_tree(weights: dict, cfg) -> dict:
    """The reference-layout ``weights`` as the program's parameter tree."""
    from repro.models import transformer

    if cfg.n_repeats != cfg.n_layers or cfg.tail_layers or len(cfg.block_pattern) != 1:
        raise SystemExit("bench: the reference covers one repeated dense attention layer")
    layer: dict = {}
    for name, path in PROGRAM_PATHS.items():
        d = layer
        for p in path[:-1]:
            d = d.setdefault(p, {})
        d[path[-1]] = weights["layers"][name]
    tree = {"embed": weights["embed"], "body": {"layer0": layer}, "final_norm": weights["final_norm"]}
    if "lm_head" in weights:
        tree["lm_head"] = weights["lm_head"]
    want = jax.eval_shape(lambda k: transformer.init_params(cfg, k), jax.random.PRNGKey(0))
    got_s = jax.tree.map(lambda x: (x.shape, x.dtype), tree)
    want_s = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got_s != want_s:
        raise SystemExit(f"bench: the program's parameter tree changed:\n{want_s}\nagainst the benchmark's\n{got_s}")
    return tree


def reference_tree(program_params: dict) -> dict:
    """The program's parameter tree (or a tree of the same layout, such as an
    optimizer moment) in the reference's layout."""
    layer = program_params["body"]["layer0"]
    out = {"embed": program_params["embed"], "final_norm": program_params["final_norm"], "layers": {}}
    for name, path in PROGRAM_PATHS.items():
        x = layer
        for p in path:
            x = x[p]
        out["layers"][name] = x
    if "lm_head" in program_params:
        out["lm_head"] = program_params["lm_head"]
    return out


@jax.jit
def leaf_norms(tree: dict) -> dict:
    """Float32 norm of every leaf; each stacked layer leaf gives one norm per layer."""

    def norm(path, x):
        x = x.astype(jnp.float32)
        if path[0].key == "layers":
            return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        return jnp.sqrt(jnp.sum(x * x))[None]

    return jax.tree_util.tree_map_with_path(norm, tree)


@jax.jit
def diff_norms(a: dict, b: dict) -> dict:
    return leaf_norms(jax.tree.map(lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def flat_norms(norms: dict) -> dict:
    """{leaf name, with ``[layer]`` for stacked layer leaves: float norm}."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(norms)[0]:
        name = "/".join(p.key for p in path)
        values = np.asarray(x, np.float64)
        if path[0].key == "layers":
            out.update({f"{name}[{i}]": float(v) for i, v in enumerate(values)})
        else:
            out[name] = float(values[0])
    return out
