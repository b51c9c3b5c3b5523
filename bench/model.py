"""The benchmark's own weights, and the work a forward pass of the paper's
GraphSAGE stack requires.

Weights are made on the device in one jitted call from ``--seed`` and are
the same for the program and the reference: ``install`` writes them into
the program's parameter tree (only leaves the benchmark made; the packed
codes are the benchmark's too, see ``bench.graphs``), and the reference
calls ``init`` again.

The work functions count the operations the model needs, independent of
how the program computes them: the decode is ``m`` row additions per node
(a one-hot contraction computes the same sum with ``c`` times the work),
the decoder MLP and the SAGE layers are their dense products.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def mlp_dims(mc: Dict):
    dims = [mc["d_c"]] + [mc["d_m"]] * (mc["n_layers"] - 1) + [mc["d_e"]]
    return list(zip(dims[:-1], dims[1:]))


def shapes(mc: Dict) -> Dict[str, tuple]:
    """Every trainable leaf of the model, by the benchmark's names."""
    out = {"codebooks": (mc["m"], mc["c"], mc["d_c"])}
    for i, (a, b) in enumerate(mlp_dims(mc)):
        out[f"mlp_w{i}"] = (a, b)
        out[f"mlp_b{i}"] = (b,)
    d_e, h = mc["d_e"], mc["hidden"]
    out.update(sage_w1=(2 * d_e, h), sage_b1=(h,), sage_w2=(2 * h, h),
               sage_b2=(h,), out_w=(h, mc["n_classes"]),
               out_b=(mc["n_classes"],))
    return out


def seed_words(seed: int) -> np.ndarray:
    """Two 32-bit words from a seed of any size (a PRNG key's raw data)."""
    return np.random.SeedSequence(int(seed)).generate_state(2).astype(np.uint32)


def init(seed: int, mc: Dict):
    """f32 weights from ``seed``, on the default device, in one call.
    Codebook entries have scale 1/sqrt(m) (so a decoded row has unit
    scale), dense weights 1/sqrt(fan_in), biases a tenth of that."""
    import jax
    import jax.numpy as jnp
    sh = shapes(mc)

    @jax.jit
    def make(raw):
        key = jax.random.wrap_key_data(raw)
        out = {}
        for i, (name, s) in enumerate(sorted(sh.items())):
            k = jax.random.fold_in(key, i)
            if name == "codebooks":
                scale = 1.0 / np.sqrt(mc["m"])
            elif len(s) == 2:
                scale = 1.0 / np.sqrt(s[0])
            else:
                scale = 0.1 / np.sqrt(s[0])
            out[name] = jax.random.normal(k, s, jnp.float32) * scale
        return out

    return make(jnp.asarray(seed_words(seed)))


def program_paths(mc: Dict) -> Dict[str, tuple]:
    """Where each benchmark leaf sits in the program's parameter tree."""
    out = {"codebooks": ("embed", "decoder", "codebooks")}
    for i in range(len(mlp_dims(mc))):
        out[f"mlp_w{i}"] = ("embed", "decoder", "mlp", f"w{i}")
        out[f"mlp_b{i}"] = ("embed", "decoder", "mlp", f"b{i}")
    out.update(sage_w1=("w1",), sage_b1=("b1",), sage_w2=("w2",),
               sage_b2=("b2",), out_w=("w_out",), out_b=("b_out",))
    return out


def get_path(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def to_bench(program_tree, mc: Dict) -> Dict:
    """The benchmark-named leaves of a program parameter (or moment) tree."""
    return {k: get_path(program_tree, p) for k, p in program_paths(mc).items()}


def install(program_params, weights: Dict, codes, mc: Dict):
    """A copy of the program's parameter tree holding the benchmark's
    weights and codes, each leaf placed like the one it replaces."""
    import jax

    def put(value, like):
        if tuple(value.shape) != tuple(like.shape) or value.dtype != like.dtype:
            raise ValueError(f"leaf {value.shape} {value.dtype} does not fit "
                             f"{like.shape} {like.dtype}")
        return jax.device_put(value, like.sharding)

    def copy(t):
        return {k: copy(v) for k, v in t.items()} if isinstance(t, dict) else t

    out = copy(program_params)
    leaves = dict(weights, codes_buf=codes)
    paths = dict(program_paths(mc), codes_buf=("embed", "codes_buf"))
    for name, path in paths.items():
        parent = get_path(out, path[:-1])
        parent[path[-1]] = put(leaves[name], parent[path[-1]])
    return out


# -- work ------------------------------------------------------------------

def decode_flops_per_row(mc: Dict) -> int:
    return mc["m"] * mc["d_c"]


def mlp_flops_per_row(mc: Dict) -> int:
    return 2 * sum(a * b for a, b in mlp_dims(mc))


def sage_flops_per_target(mc: Dict) -> int:
    """Both SAGE layers, the mean aggregations and the head, per target."""
    f1, f2 = mc["fanout"], mc["fanout"]
    d_e, h, c = mc["d_e"], mc["hidden"], mc["n_classes"]
    layer1 = (1 + f1) * (2 * 2 * d_e * h + (f2 + 1) * d_e)
    layer2 = 2 * 2 * h * h + f1 * h
    return layer1 + layer2 + 2 * h * c


def forward_flops(mc: Dict, decoded_rows: float, targets: float) -> float:
    return (decoded_rows * (decode_flops_per_row(mc) + mlp_flops_per_row(mc))
            + targets * sage_flops_per_target(mc))


def decode_bytes(mc: Dict, rows: float) -> float:
    """Packed codes in, each codebook once, f32 rows out."""
    n_words = -(-mc["m"] * (int(mc["c"]).bit_length() - 1) // 32)
    return rows * (4 * n_words + 4 * mc["d_c"]) + 4 * mc["m"] * mc["c"] * mc["d_c"]
