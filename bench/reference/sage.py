"""Plain reference of the paper's GraphSAGE with hashed node embeddings.

Written from the paper (§3.2 decoder, Figure 4 GraphSAGE, §C.1 sizes) in
``jax.numpy``, float32, and independent of the program: it takes the graph,
the codes and the weights from the benchmark, and from the program only the
node ids it sampled.

    decode   h = sum_j codebooks[j, code_j]             (m row additions)
    MLP      l linear layers d_c -> d_m -> ... -> d_e, ReLU between
    SAGE     z = relu([mean(neighbours), self] @ W + b), two layers
    head     logits = z @ W_out + b_out; loss = mean cross-entropy
    AdamW    PyTorch's defaults for the betas and eps, decoupled decay

``prec`` sets the precision of every product:

    "highest"  float32 products (the precision the configurations state)
    "high"     the control: each float32 operand split into two bfloat16
               parts and three of the four part products kept, which is
               what a TPU's three-pass matmul computes; codebook rows and
               the codebook gradient are rounded to those two parts
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(x):
    # an explicit rounding op: the compiler may fold away a round trip
    # through a bfloat16 array (excess precision), never reduce_precision
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _parts(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def _round2(x):
    hi, lo = _parts(x)
    return hi + lo


def _mm3(a, b):
    (ah, al), (bh, bl) = _parts(a), _parts(b)
    mm = partial(jnp.matmul, precision=HIGHEST)
    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


@jax.custom_vjp
def _matmul_high(a, b):
    return _mm3(a, b)


def _mh_fwd(a, b):
    return _mm3(a, b), (a, b)


def _mh_bwd(res, g):
    a, b = res
    return _mm3(g, b.T), _mm3(a.T, g)


_matmul_high.defvjp(_mh_fwd, _mh_bwd)


@jax.custom_vjp
def _round_grad(x):
    return x


_round_grad.defvjp(lambda x: (x, None), lambda _, g: (_round2(g),))


def matmul(a, b, prec: str):
    if prec == "highest":
        return jnp.matmul(a, b, precision=HIGHEST)
    if prec == "high":
        lead = a.shape[:-1]
        return _matmul_high(a.reshape(-1, a.shape[-1]), b).reshape(*lead, b.shape[-1])
    raise ValueError(f"unknown precision {prec!r}")


def unpack(words, c: int, m: int):
    """(n, n_words) uint32 in the program's storage layout -> (n, m) int32."""
    b = int(c).bit_length() - 1
    bits = (words[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    bits = bits.reshape(words.shape[0], -1)[:, :m * b].reshape(-1, m, b)
    return (bits.astype(jnp.int32) << jnp.arange(b - 1, -1, -1)).sum(-1)


def decode(params, codes, mc: Dict, prec: str):
    """codes (U, m) -> node embeddings (U, d_e)."""
    cb = params["codebooks"]
    if prec == "high":
        cb = _round_grad(_round2(cb))
    h = cb[0][codes[:, 0]]
    for j in range(1, mc["m"]):
        h = h + cb[j][codes[:, j]]
    n = mc["n_layers"]
    for i in range(n):
        h = matmul(h, params[f"mlp_w{i}"], prec) + params[f"mlp_b{i}"]
        if i < n - 1:
            h = jax.nn.relu(h)
    return h


def sage(params, hu, maps: Sequence, prec: str):
    """hu (U, d_e) rows of the unique nodes; maps index targets (B,),
    first neighbours (B, f1) and second neighbours (B, f1, f2) into hu."""
    h0, h1, h2 = hu[maps[0]], hu[maps[1]], hu[maps[2]]

    def layer(agg, own, w, b):
        return jax.nn.relu(matmul(jnp.concatenate([agg, own], -1), params[w], prec)
                           + params[b])

    z0 = layer(h1.mean(1), h0, "sage_w1", "sage_b1")
    z1 = layer(h2.mean(2), h1, "sage_w1", "sage_b1")
    z = layer(z1.mean(1), z0, "sage_w2", "sage_b2")
    return matmul(z, params["out_w"], prec) + params["out_b"]


def logits(params, words, unique, maps, mc: Dict, prec: str):
    codes = unpack(words[unique], mc["c"], mc["m"])
    return sage(params, decode(params, codes, mc, prec), maps, prec)


def loss(params, words, unique, maps, labels, mc: Dict, prec: str,
         keep: float = 1.0):
    """Mean cross-entropy over the targets; ``keep`` < 1 plants a fault:
    only that leading share of the batch is counted."""
    lg = logits(params, words, unique, maps, mc, prec)
    if keep < 1.0:
        n = int(lg.shape[0] * keep)
        lg, labels = lg[:n], labels[:n]
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def adamw(params, grads, opt, step: int, oc: Dict):
    """One AdamW step; ``opt`` holds ``mu`` and ``nu``; ``step`` counts from 1."""
    b1, b2, lr, eps, wd = oc["b1"], oc["b2"], oc["lr"], oc["eps"], oc["weight_decay"]
    c1, c2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    mu = {k: b1 * opt["mu"][k] + (1 - b1) * g for k, g in grads.items()}
    nu = {k: b2 * opt["nu"][k] + (1 - b2) * g * g for k, g in grads.items()}
    new = {k: p - lr * ((mu[k] / c1) / (jnp.sqrt(nu[k] / c2) + eps) + wd * p)
           for k, p in params.items()}
    return new, {"mu": mu, "nu": nu}


def dedup(levels: Sequence[np.ndarray], pad_to: int = 16384):
    """Unique node ids of sampled levels, padded to a multiple of ``pad_to``
    by repeating the first (so blocks of similar size share one compiled
    program), and each level's index into them."""
    flat = np.concatenate([np.asarray(l).ravel() for l in levels])
    uniq, inv = np.unique(flat, return_inverse=True)
    uniq = np.concatenate([uniq, np.full(-len(uniq) % pad_to, uniq[0])])
    maps, off = [], 0
    for l in levels:
        maps.append(inv[off:off + l.size].reshape(l.shape).astype(np.int32))
        off += l.size
    return uniq.astype(np.int32), maps


def train(params, words, labels_all, batches, mc: Dict, oc: Dict,
          prec: str = "highest"):
    """Follows the program through ``len(batches)`` AdamW steps.  Each batch
    is the list of sampled levels [targets, first, second neighbours].
    Returns the losses, the first step's gradient and the final params."""
    words = jnp.asarray(words)
    labels_all = jnp.asarray(labels_all)
    vg = jax.jit(jax.value_and_grad(partial(loss, mc=mc, prec=prec)))
    step_fn = jax.jit(partial(adamw, oc=oc))
    opt = {"mu": {k: jnp.zeros_like(v) for k, v in params.items()},
           "nu": {k: jnp.zeros_like(v) for k, v in params.items()}}
    losses, first_grad = [], None
    for i, levels in enumerate(batches):
        uniq, maps = dedup(levels)
        l, g = vg(params, words, jnp.asarray(uniq), [jnp.asarray(m) for m in maps],
                  labels_all[jnp.asarray(levels[0])])
        losses.append(float(l))
        if first_grad is None:
            first_grad = jax.device_get(g)
        params, opt = step_fn(params, g, opt, jnp.float32(i + 1))
    return losses, first_grad, jax.device_get(params)


def serve_logits(params, words, requests, mc: Dict, prec: str = "highest",
                 block_rows: int = 1 << 20, pad_to: int = 1 << 16):
    """Logits of each request's targets from its sampled levels, computed
    in blocks of requests of at most ``block_rows`` sampled slots."""
    words = jnp.asarray(words)
    fn = jax.jit(partial(logits, mc=mc, prec=prec))
    out, i = [], 0
    while i < len(requests):
        j, slots = i, 0
        while j < len(requests) and (j == i or slots + requests[j][2].size <= block_rows):
            slots += sum(np.asarray(l).size for l in requests[j])
            j += 1
        block = requests[i:j]
        levels = [np.concatenate([r[k] for r in block]) for k in range(3)]
        uniq, maps = dedup(levels, pad_to)
        lg = np.asarray(fn(params, words, jnp.asarray(uniq),
                           [jnp.asarray(m) for m in maps]))
        off = 0
        for r in block:
            out.append(lg[off:off + r[0].shape[0]])
            off += r[0].shape[0]
        i = j
    return out
