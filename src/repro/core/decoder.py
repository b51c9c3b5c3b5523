"""Decoder model (paper §3.2, Figure 2).

codes (B, m) ints in [0, c)
  -> retrieve one vector per codebook (m codebooks, each (c, d_c))
  -> sum the m vectors
  -> light variant: elementwise-rescale by trainable W0 (codebooks frozen)
     full  variant: no W0 (codebooks trainable)
  -> l-layer MLP with ReLU between linear layers: d_c -> d_m -> ... -> d_e

TPU adaptation: the codebook retrieval + W0 scale is a
``repro.core.backend.DecodeBackend`` selected by ``lookup_impl`` ("gather" |
"onehot" | "pallas" | "auto"); see that module for the implementations and
the registration hook for new ones.

``lookup_impl`` also selects the *compression family* — how the decode-stage
parameters are laid out (``core.backend.family_of``, docs/decode_backends.md
§Compression families):

  paper    (default) m dense codebooks ``(m, c, d_c)``, the scheme above.
  hashemb  shared pools ``(m, c, d_c)`` + per-position weights ``wpos
           (m, d_c)`` (arXiv:2109.00101).  ``apply_decoder`` folds ``wpos``
           into the pools before the decode (exact:
           ``sum_j (wpos[j]*P[j])[h_j] == sum_j wpos[j]*P[j][h_j]``), so any
           base backend serves the gather.  light = frozen ``pools_buf`` +
           trainable ``wpos``.
  tt       TT core pair ``tt_g0 (m, c1, d1, r)`` / ``tt_g1 (m, c2, r, d2)``
           with ``c = c1*c2``, ``d_c = d1*d2`` (Nimble GNN,
           arXiv:2206.10581); the rank-``tt_rank`` contraction is fused into
           ``TTBackend.decode``.  light = frozen ``tt_g0_buf``/``tt_g1_buf``
           + trainable ``w0``.

Codes placement is invisible here: every backend consumes *unpacked* codes
``(B, m)``, and whether those came from a device-resident ``codes_buf``
gather or from batch-carried rows (``codes_placement="host"``, see
``core.embedding.embed_lookup``) the bit pattern entering ``apply_decoder``
is identical — which is why host offload is bitwise-exact on every backend.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.backend import DecodeBackend, family_of, get_backend, \
    tt_factor_pair
from repro.nn import module as nn
from repro.parallel import sharding

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    c: int = 256           # code cardinality
    m: int = 16            # code length
    d_c: int = 512         # codebook vector dim
    d_m: int = 512         # MLP hidden dim
    d_e: int = 64          # output embedding dim
    n_layers: int = 3      # number of linear layers (paper's l)
    variant: str = "full"  # "full" (trainable codebooks) | "light" (frozen + W0)
    lookup_impl: str = "onehot"  # backend name, may select a family (see above)
    compute_dtype: str = "bfloat16"
    # Decode precision knobs (core.backend.MixedPrecisionPolicy): storage
    # dtype of codebooks/w0 entering the decode (None = compute_dtype) and
    # optional absmax-int8 codebook quantization with fused dequant.
    param_dtype: Optional[str] = None
    quantize: str = "none"     # "none" | "int8"
    tt_rank: int = 8           # TT rank r ("tt" family only)

    @property
    def family(self) -> str:
        return family_of(self.lookup_impl)

    def tt_dims(self) -> Tuple[int, int, int, int]:
        """(c1, c2, d1, d2): the balanced code/feature splits of the ``tt``
        family's core pair."""
        c1, c2 = tt_factor_pair(self.c)
        d1, d2 = tt_factor_pair(self.d_c)
        return c1, c2, d1, d2

    def precision_policy(self) -> "MixedPrecisionPolicy":
        from repro.core.backend import MixedPrecisionPolicy
        return MixedPrecisionPolicy(
            param_dtype=self.param_dtype or self.compute_dtype,
            compute_dtype=self.compute_dtype,
            reduce_dtype="float32",
            quantize=self.quantize,
        )

    def _decode_stage_params(self) -> int:
        """Parameter count of the decode-stage table (family-dependent)."""
        if self.family == "tt":
            c1, c2, d1, d2 = self.tt_dims()
            return self.m * self.tt_rank * (c1 * d1 + c2 * d2)
        return self.m * self.c * self.d_c    # paper codebooks / hashemb pools

    def trainable_params(self) -> int:
        """Closed-form trainable-parameter count (paper §3.2, extended to
        the alternate families); matches ``nn.param_count(params, True)``."""
        mlp = self.d_c * self.d_m + max(self.n_layers - 2, 0) * self.d_m**2 + self.d_m * self.d_e
        if self.n_layers == 1:
            mlp = self.d_c * self.d_e
        fam = self.family
        if fam == "hashemb":
            wpos = self.m * self.d_c
            if self.variant == "light":
                return wpos + mlp
            return self._decode_stage_params() + wpos + mlp
        if self.variant == "light":
            return self.d_c + mlp
        return self._decode_stage_params() + mlp

    def frozen_params(self) -> int:
        return self._decode_stage_params() if self.variant == "light" else 0


def _mlp_dims(cfg: DecoderConfig):
    if cfg.n_layers == 1:
        return [(cfg.d_c, cfg.d_e)]
    dims = [(cfg.d_c, cfg.d_m)]
    dims += [(cfg.d_m, cfg.d_m)] * (cfg.n_layers - 2)
    dims += [(cfg.d_m, cfg.d_e)]
    return dims


def _init_decode_stage(ks, cfg: DecoderConfig) -> nn.Params:
    """Family-dependent decode-stage parameters (the ``light`` variant
    freezes the table via the ``_buf`` key convention and trains only the
    small rescale: ``w0`` / ``wpos``)."""
    if cfg.variant not in ("light", "full"):
        raise ValueError(f"unknown decoder variant {cfg.variant!r}")
    light = cfg.variant == "light"
    params: nn.Params = {}
    if cfg.family == "hashemb":
        pools = nn.dense_init(ks["codebooks"], (cfg.m, cfg.c, cfg.d_c),
                              scale=1.0 / jnp.sqrt(cfg.m))
        params["pools_buf" if light else "pools"] = sharding.logical(
            pools, None, None, "codebook")
        # wpos = 1 makes the init decode the plain pool sum (same
        # distribution as the paper codebooks); always trainable — in the
        # light variant it IS the per-position W0 analogue
        params["wpos"] = jnp.ones((cfg.m, cfg.d_c), jnp.float32)
        return params
    if cfg.family == "tt":
        c1, c2, d1, d2 = cfg.tt_dims()
        r = cfg.tt_rank
        # materialized entries are sums of r products of two core factors;
        # factor std s gives entry var ~ r*s^4, so s = (m*r)^(-1/4) matches
        # the paper codebooks' 1/sqrt(m) entry scale
        s = float((cfg.m * r) ** -0.25)
        k0, k1 = jax.random.split(ks["codebooks"])
        g0 = nn.dense_init(k0, (cfg.m, c1, d1, r), scale=s)
        g1 = nn.dense_init(k1, (cfg.m, c2, r, d2), scale=s)
        params["tt_g0_buf" if light else "tt_g0"] = sharding.logical(
            g0, None, None, "codebook", None)
        params["tt_g1_buf" if light else "tt_g1"] = sharding.logical(
            g1, None, None, None, "codebook")
        if light:
            params["w0"] = jnp.ones((cfg.d_c,), jnp.float32)
        return params
    cb = nn.dense_init(ks["codebooks"], (cfg.m, cfg.c, cfg.d_c), scale=1.0 / jnp.sqrt(cfg.m))
    cb = sharding.logical(cb, None, None, "codebook")
    if light:
        params["codebooks_buf"] = cb           # frozen (stored off-accelerator in Table 2)
        params["w0"] = jnp.ones((cfg.d_c,), jnp.float32)
    else:
        params["codebooks"] = cb
    return params


def _decode_stage_operands(params: nn.Params, cfg: DecoderConfig, pdtype):
    """Extract the backend's ``(codebooks, w0)`` operands from the params,
    cast to the policy's storage dtype.  hashemb folds ``wpos`` into the
    pools here (exact in f32, differentiable to both factors), so every
    backend sees the standard dense layout; tt hands the core pair through
    as a pytree."""
    light = cfg.variant == "light"
    if cfg.family == "hashemb":
        pools = params["pools_buf" if light else "pools"]
        cb = (pools.astype(jnp.float32)
              * params["wpos"].astype(jnp.float32)[:, None, :]).astype(pdtype)
        return cb, None
    if cfg.family == "tt":
        cb = (params["tt_g0_buf" if light else "tt_g0"].astype(pdtype),
              params["tt_g1_buf" if light else "tt_g1"].astype(pdtype))
        w0 = params["w0"].astype(pdtype) if light else None
        return cb, w0
    cb = params["codebooks_buf" if light else "codebooks"].astype(pdtype)
    w0 = params["w0"].astype(pdtype) if light else None
    return cb, w0


def init_decoder(key: jax.Array, cfg: DecoderConfig) -> nn.Params:
    ks = nn.split_keys(key, ["codebooks", "w0", "mlp"])
    params = _init_decode_stage(ks, cfg)
    mlp_keys = jax.random.split(ks["mlp"], cfg.n_layers)
    params["mlp"] = {
        f"w{i}": nn.dense_init(mlp_keys[i], dims)
        for i, dims in enumerate(_mlp_dims(cfg))
    }
    params["mlp"].update(
        {f"b{i}": jnp.zeros((dims[1],), jnp.float32) for i, dims in enumerate(_mlp_dims(cfg))}
    )
    return params


def apply_decoder(
    params: nn.Params,
    codes: Array,
    cfg: DecoderConfig,
    *,
    interpret: bool = False,
    backend: Optional[DecodeBackend] = None,
    plan=None,
) -> Array:
    """codes (..., m) int32 -> embeddings (..., d_e).

    ``backend`` overrides the config's ``lookup_impl`` (call-sites that hold
    a resolved backend — the graph engine, benchmarks — pass it straight
    through instead of re-resolving per call).  ``plan`` is an optional
    ``graph.sampler.OwnerPlan`` for the owner-computes cross-shard decode;
    backends that can't exploit it ignore it (decoding every row is always
    correct)."""
    lead = codes.shape[:-1]
    codes2d = codes.reshape(-1, cfg.m)
    dtype = jnp.dtype(cfg.compute_dtype)
    policy = cfg.precision_policy()
    pdtype = jnp.dtype(policy.param_dtype)

    cb, w0 = _decode_stage_operands(params, cfg, pdtype)

    be = backend if backend is not None else get_backend(
        cfg.lookup_impl, interpret=interpret, policy=policy)
    # named scopes label the device ops (and their transposes) in a trace
    with jax.named_scope("decode"):
        if plan is not None and hasattr(be, "decode_frontier"):
            h = be.decode_frontier(codes2d, cb, w0, plan=plan).astype(dtype)
        else:
            h = be.decode(codes2d, cb, w0).astype(dtype)

    mlp = params["mlp"]
    with jax.named_scope("decoder_mlp"):
        for i in range(cfg.n_layers):
            h = h @ mlp[f"w{i}"].astype(dtype) + mlp[f"b{i}"].astype(dtype)
            if i < cfg.n_layers - 1:
                h = jax.nn.relu(h)
    return h.reshape(*lead, cfg.d_e)
