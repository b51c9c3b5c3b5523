"""Pluggable decode backends for the paper's hot op (codes -> codebook sum).

Every call-site that rebuilds a node/token embedding from its m hash codes —
the embedding layer, the GNN frontier decode, the LM input path and serving —
routes through one ``DecodeBackend``:

    decode(codes (B, m) int32, codebooks (m, c, d_c), w0 (d_c,)?) -> (B, d_c) f32

Four implementations are registered:

  gather   m sequential gathers accumulated in f32 — the paper's GPU
           formulation and the bit-exactness oracle (accumulation order
           matches the Pallas kernel's, so kernel parity is bitwise).
  onehot   one (B, m*c) x (m*c, d_c) matmul with f32 accumulation — the MXU
           formulation XLA fuses well.
  pallas   ``kernels.hash_decode`` fused kernel.  Unaligned ``B``/``d_c`` are
           explicitly zero-padded to tile/block multiples here (a warning is
           emitted once) instead of silently falling back to the reference
           path.
  sharded  data-parallel decode: frontier rows partitioned over the active
           mesh's data axis, decoded shard-local (``shard_map``) by a base
           backend (``"sharded:gather"`` pins it), rows all_gathered forward
           and codebook/W0 cotangents psummed in the custom VJP.
  owner    owner-computes cross-shard dedup: rows hash-partitioned by
           ``node_id % n_shards``, requests ``all_to_all``ed to their owner,
           each distinct owned id decoded exactly once, embeddings
           ``all_to_all``ed back (routing = a host-built static-capacity
           ``graph.sampler.OwnerPlan`` riding on the batch).

Two further entries select alternate *compression families* (ROADMAP item
4) rather than alternate execution strategies — same registry, same
frontier/dedup/cache/owner machinery, different parameterization (see
``family_of`` and docs/decode_backends.md §Compression families):

  hashemb  position-based hash embeddings (arXiv:2109.00101): each id maps
           through m independent hash functions into shared parameter
           pools combined with learned per-position weights.  No per-entity
           ``codes_buf`` exists — codes are recomputed from the id per
           lookup (``core.codes.position_codes``).  The pool gather itself
           is delegated to a base backend (``"hashemb:gather"`` pins it),
           so the decode math rides gather/onehot/pallas unchanged.
  tt       tensor-train factorized codebooks (Nimble GNN, arXiv:2206.10581):
           the (m, c, d_c) codebook tensor is stored as two TT cores
           ``g0 (m, c1, d1, r)`` / ``g1 (m, r, c2, d2)`` with
           ``c = c1*c2``, ``d_c = d1*d2``; the rank-r contraction is fused
           into the decode (gather both cores' rows, one einsum) — the
           full codebook is never materialized.

Selection is by config string (``lookup_impl``): a backend name, or ``auto``
which under a multi-device mesh picks ``owner`` when the measured frontier
duplication beats ``OWNER_DUP_THRESHOLD`` (else ``sharded``), ``pallas`` on
TPU-capable runtimes and ``onehot`` otherwise.  New backends register via
``register_backend`` and become selectable by name everywhere at once.

``CachedDecodeBackend`` layers a device-resident LRU of *decoded embeddings*
keyed by entity id on top of any base decode path: hot (high-degree) nodes
recur in almost every GNN frontier, and their embeddings only drift as fast
as the decoder parameters train.  A ``staleness`` budget (in codebook
versions; the train step bumps the version on every optimizer update) bounds
that drift; at staleness 0 every access re-decodes, reproducing the uncached
computation exactly.

Every backend carries a ``MixedPrecisionPolicy`` (param_dtype /
compute_dtype / reduce_dtype / quantize) and states its dtype contract via
``dtype_contract()``: codebooks may be stored bf16 or absmax-int8 (fused
dequant in the pallas kernel, straight-through dequant in the XLA
backends), but accumulation — the kernel's MXU accumulator, every psum and
every scatter-add on the VJP path — is always ``reduce_dtype`` (f32).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jnp.ndarray

# f32 min tile on TPU is (8, 128): sublane multiple for the batch dim, lane
# multiple for the feature dim (pallas guide, "Tiling Constraints").
_SUBLANE = 8
_LANE = 128

_warned: set = set()


def _warn_once(key: str, msg: str) -> None:
    if key not in _warned:
        _warned.add(key)
        warnings.warn(msg, stacklevel=3)


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


@dataclasses.dataclass(frozen=True)
class BackendCapabilities:
    """Metadata consumed by selection logic and call-sites."""
    grad: bool = True            # differentiable w.r.t. codebooks / w0
    fused: bool = False          # single fused kernel (no HBM intermediates)
    accelerator: Tuple[str, ...] = ("cpu", "gpu", "tpu")


@dataclasses.dataclass(frozen=True)
class MixedPrecisionPolicy:
    """Dtype contract of a decode path (the zeroband param/compute/reduce
    split, specialised to the decode hot op).

    ``param_dtype``    storage dtype of codebooks/w0 entering the decode
                       (None = use whatever the caller passed — the
                       pre-policy behaviour, bit-exact with old configs)
    ``compute_dtype``  activation dtype the caller works in (informational
                       here — the decode itself always accumulates f32 and
                       returns f32; callers cast the output down)
    ``reduce_dtype``   accumulation dtype: the kernel's MXU accumulator and
                       every psum / scatter-add on the VJP path.  Always
                       float32 — backends hard-code it and tests assert it;
                       the field exists so the contract is stated, not
                       implied.
    ``quantize``       "none" | "int8": absmax per-(codebook, code) int8
                       values + f32 scales.  Fused dequant in the pallas
                       kernel; straight-through dequant-identity in the XLA
                       backends (bitwise-matching values, see
                       kernels.hash_decode.ops).
    """
    param_dtype: Optional[str] = None
    compute_dtype: Optional[str] = None
    reduce_dtype: str = "float32"
    quantize: str = "none"

    def __post_init__(self):
        if self.quantize not in ("none", "int8"):
            raise ValueError(
                f"quantize={self.quantize!r} not supported (expected 'none' "
                f"or 'int8'; int4 packing is a documented future extension)")
        if self.reduce_dtype != "float32":
            raise ValueError(
                "reduce_dtype must be 'float32': every backend accumulates "
                "and reduces in f32 (that is the stated contract)")


DEFAULT_POLICY = MixedPrecisionPolicy()

# Documented decode drift bounds vs the all-f32 path (docs/decode_backends.md
# dtype-contract table): max-abs output error <= bound * max-abs(f32 output)
# per decode, and end-to-end step-0 loss relative drift within the same
# bound, for EVERY backend (incl. owner and cached) — tests/test_precision.py
# asserts both, the CI bench gate asserts the int8 one.
DRIFT_BOUNDS = {"bfloat16": 1.5e-2, "int8": 5e-2}


class DecodeBackend:
    """Protocol: subclasses set ``name``/``capabilities``/``preferred_pad``
    and implement ``decode``.  ``preferred_pad`` is the batch multiple the
    backend runs best at — frontier padding (``pad_to``) should be a multiple
    of it so the hot path never hits the padding fix-up.  ``policy`` is the
    backend's ``MixedPrecisionPolicy``; the default (all-None) is a no-op
    cast-wise, so legacy construction sites keep bit-exact numerics."""

    name: str = "abstract"
    capabilities = BackendCapabilities()
    preferred_pad: int = 1
    policy: MixedPrecisionPolicy = DEFAULT_POLICY

    def decode(self, codes: Array, codebooks: Array,
               w0: Optional[Array] = None) -> Array:
        raise NotImplementedError

    def feature_dim(self, codebooks) -> int:
        """Output feature dim ``d_c`` of ``decode`` given its ``codebooks``
        operand.  The default reads the dense layout ``(m, c, d_c)``;
        family backends whose codebooks are a pytree (``tt``) override it.
        Collective wrappers use this instead of ``codebooks.shape[2]`` so
        they stay layout-agnostic."""
        return int(codebooks.shape[2])

    def _prep(self, codebooks, w0: Optional[Array]):
        """Cast params to the policy's storage dtype (simulating bf16 HBM
        residency); int8 handling is backend-specific — fused scales in
        pallas, straight-through dequant in the XLA backends — so it is NOT
        applied here.  ``codebooks`` may be a pytree (the ``tt`` family's
        core pair); every leaf is cast."""
        p = self.policy
        if p.param_dtype is not None:
            codebooks = jax.tree_util.tree_map(
                lambda x: x.astype(p.param_dtype), codebooks)
            if w0 is not None:
                w0 = w0.astype(p.param_dtype)
        return codebooks, w0

    def dtype_contract(self) -> Dict[str, str]:
        """The backend's stated dtype contract (docs/decode_backends.md)."""
        p = self.policy
        storage = ("int8 values + float32 scales" if p.quantize == "int8"
                   else (p.param_dtype or "caller-provided"))
        return {
            "backend": self.name,
            "storage": storage,
            "compute": p.compute_dtype or "float32",
            "accumulate": p.reduce_dtype,
            "output": "float32",
        }

    def decode_frontier(self, codes: Array, codebooks: Array,
                        w0: Optional[Array] = None, *, plan=None) -> Array:
        """Frontier-decode entry point: like ``decode`` but may exploit a
        host-built ``graph.sampler.OwnerPlan`` riding on the batch.  The
        default ignores the plan (decoding every row is always correct);
        only collective backends (``owner``) override it."""
        return self.decode(codes, codebooks, w0)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<DecodeBackend {self.name}>"


class GatherBackend(DecodeBackend):
    """Oracle: m sequential gathers, f32 accumulation in codebook order j=0..m-1
    (the same order the Pallas kernel accumulates in, so parity is bitwise)."""

    name = "gather"
    capabilities = BackendCapabilities(grad=True, fused=False)
    preferred_pad = 1

    def __init__(self, policy: Optional[MixedPrecisionPolicy] = None):
        self.policy = policy or DEFAULT_POLICY

    def decode(self, codes, codebooks, w0=None):
        codebooks, w0 = self._prep(codebooks, w0)
        if self.policy.quantize == "int8":
            from repro.kernels.hash_decode import ops as hd_ops
            # straight-through dequant: forward sees q·s (element-for-element
            # the same f32 products as the fused kernel), backward is the
            # identity to the float masters
            codebooks = hd_ops.quantize_dequantize(codebooks)
        m = codebooks.shape[0]
        acc = codebooks[0].astype(jnp.float32)[codes[:, 0]]
        for j in range(1, m):
            acc = acc + codebooks[j].astype(jnp.float32)[codes[:, j]]
        if w0 is not None:
            acc = acc * w0.astype(jnp.float32)[None, :]
        return acc


class OnehotBackend(DecodeBackend):
    """One-hot x stacked-codebook matmul; the sum over m is absorbed into a
    single (B, m*c) x (m*c, d_c) contraction the MXU executes natively."""

    name = "onehot"
    capabilities = BackendCapabilities(grad=True, fused=False)
    preferred_pad = _SUBLANE

    def __init__(self, policy: Optional[MixedPrecisionPolicy] = None):
        self.policy = policy or DEFAULT_POLICY

    def decode(self, codes, codebooks, w0=None):
        codebooks, w0 = self._prep(codebooks, w0)
        if self.policy.quantize == "int8":
            from repro.kernels.hash_decode import ops as hd_ops
            codebooks = hd_ops.quantize_dequantize(codebooks)
        m, c, d_c = codebooks.shape
        B = codes.shape[0]
        iota_c = jax.lax.broadcasted_iota(jnp.int32, (1, 1, c), 2)
        onehot = (codes[:, :, None] == iota_c).astype(codebooks.dtype)
        # HIGHEST: a TPU's default f32 matmul is one bf16 pass
        out = jax.lax.dot_general(
            onehot.reshape(B, m * c), codebooks.reshape(m * c, d_c),
            (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        if w0 is not None:
            out = out * w0.astype(jnp.float32)[None, :]
        return out


class PallasBackend(DecodeBackend):
    """Fused Pallas kernel with explicit padding of unaligned shapes.

    ``B`` is padded with zero codes (code 0 is always valid) up to a
    tile/block multiple; ``d_c`` is padded by zero-extending the codebooks
    (and w0) along the feature dim.  Both paths warn once — persistent
    unaligned shapes should fix their config, not eat a copy per call."""

    name = "pallas"
    capabilities = BackendCapabilities(
        grad=True, fused=True, accelerator=("tpu",))

    def __init__(self, block_b: int = 256, block_d: int = 256,
                 interpret: bool = False,
                 policy: Optional[MixedPrecisionPolicy] = None):
        self.block_b = int(block_b)
        self.block_d = int(block_d)
        self.interpret = bool(interpret)
        self.policy = policy or DEFAULT_POLICY
        self.preferred_pad = self.block_b

    def _plan(self, B: int, d_c: int) -> Tuple[int, int, int, int]:
        """Minimal padding to tile multiples, then the largest tileable
        block that divides each padded dim — shrinking the block is free,
        padding (especially the codebook copy along d_c) is not."""
        B_pad = _round_up(B, _SUBLANE)
        bb = min(self.block_b, B_pad)
        while B_pad % bb:
            bb -= _SUBLANE
        d_pad = _round_up(d_c, _LANE)
        bd = min(self.block_d, d_pad)
        while d_pad % bd:
            bd -= _LANE
        return B_pad, bb, d_pad, bd

    def decode(self, codes, codebooks, w0=None):
        from repro.kernels.hash_decode import ops as hd_ops

        codebooks, w0 = self._prep(codebooks, w0)
        B = codes.shape[0]
        d_c = codebooks.shape[2]
        B_pad, block_b, d_pad, block_d = self._plan(B, d_c)
        if B_pad != B:
            _warn_once(
                f"pallas-pad-b-{B}",
                f"pallas decode: padding batch {B} -> {B_pad}; pad frontiers "
                f"to a multiple of preferred_pad={self.preferred_pad} to "
                f"avoid the copy")
            codes = jnp.pad(codes, ((0, B_pad - B), (0, 0)))
        if d_pad != d_c:
            _warn_once(
                f"pallas-pad-d-{d_c}",
                f"pallas decode: padding d_c {d_c} -> {d_pad} (codebook "
                f"copy per call); prefer lane-aligned d_c")
            codebooks = jnp.pad(codebooks, ((0, 0), (0, 0), (0, d_pad - d_c)))
            if w0 is not None:
                w0 = jnp.pad(w0, (0, d_pad - d_c))
        out = hd_ops.hash_decode(
            codes, codebooks, w0,
            block_b=block_b, block_d=block_d, interpret=self.interpret,
            quantize=self.policy.quantize)
        return out[:B, :d_c]


# ---------------------------------------------------------------------------
# sharded (data-parallel) decode
# ---------------------------------------------------------------------------

def _replicated_specs(tree):
    """Per-leaf fully-replicated PartitionSpecs for a (possibly nested)
    codebook pytree — exact-rank ``P(None, ..., None)`` so shard_map sees
    one spec per leaf whatever the family's parameter layout is."""
    from jax.sharding import PartitionSpec as P
    return jax.tree_util.tree_map(lambda x: P(*([None] * x.ndim)), tree)


def _psum_f32(tree, like, axis):
    """reduce_dtype contract: cross-shard accumulation happens in f32 even
    when the params (and so their cotangents) are bf16.  Pytree-wide."""
    return jax.tree_util.tree_map(
        lambda g, p: jax.lax.psum(g.astype(jnp.float32), axis).astype(p.dtype),
        tree, like)


def _sharded_decode(base: DecodeBackend, mesh, axis: str,
                    codes: Array, codebooks, w0: Array) -> Array:
    """Row-partitioned decode under ``shard_map``: each device decodes its
    block of frontier rows against the replicated codebooks, the forward
    ``all_gather``s the decoded rows, and the custom VJP ``psum``s the
    codebook/W0 cotangents so the replicated parameters see the full-batch
    gradient.  (shard_map with ``check_vma=False`` does not insert the
    replicated-input psum itself — spelling the VJP out keeps gradients
    correct by construction.)  ``codebooks`` may be any pytree the base
    backend understands (dense ``(m, c, d_c)``, or the ``tt`` core pair)."""
    from jax.sharding import PartitionSpec as P

    cb_specs = _replicated_specs(codebooks)

    @jax.custom_vjp
    def decode(codes, cb, w0):
        def local(codes_l, cb_, w0_):
            out_l = base.decode(codes_l, cb_, w0_)
            return jax.lax.all_gather(out_l, axis, axis=0, tiled=True)
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis, None), cb_specs, P(None)),
            out_specs=P(None, None), check_vma=False)(codes, cb, w0)

    def fwd(codes, cb, w0):
        return decode(codes, cb, w0), (codes, cb, w0)

    def bwd(res, g):
        codes, cb, w0 = res

        def local(codes_l, g_l, cb_, w0_):
            _, vjp = jax.vjp(
                lambda c, s: base.decode(codes_l, c, s), cb_, w0_)
            gcb, gw0 = vjp(g_l)
            gcb = _psum_f32(gcb, cb_, axis)
            gw0 = jax.lax.psum(gw0.astype(jnp.float32), axis).astype(w0_.dtype)
            return gcb, gw0

        gcb, gw0 = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis, None), P(axis, None), cb_specs, P(None)),
            out_specs=(cb_specs, P(None)),
            check_vma=False)(codes, g, cb, w0)
        return None, gcb, gw0      # codes are integers: no gradient

    decode.defvjp(fwd, bwd)
    return decode(codes, codebooks, w0)


def _active_mesh_axis(mesh, axis):
    """Resolve the (mesh, data-axis) pair a collective backend runs over:
    the pinned mesh if any, else the ``use_sharding`` context's at trace
    time; ``(None, None)`` means single-device (degrade to base)."""
    from repro.parallel import sharding as sh
    mesh = mesh if mesh is not None else sh.current_mesh()
    if mesh is None:
        return None, None
    return mesh, (axis or sh.data_axis(mesh))


def _check_collective_base(name: str, base) -> None:
    if isinstance(base, str) and base.split(":")[0] in ("sharded", "owner"):
        raise ValueError(
            f"{name} backend cannot wrap itself or another collective "
            f"backend (got base={base!r})")


class ShardedBackend(DecodeBackend):
    """Data-parallel decode: frontier rows are partitioned across the mesh's
    data axis and decoded shard-local by a wrapped base backend (each shard's
    batch source already groups its rows contiguously, so no resharding
    happens on the hot path).  Codebooks stay replicated — they are ≤ 10 MB,
    which IS the paper's point; what doesn't fit one host at industrial scale
    is the *frontier decode work*, and that is what shards here.

    The mesh is read from the ``use_sharding`` context at trace time (or
    pinned via ``mesh=``); with no mesh or a 1-sized data axis the backend
    degrades to a plain base-backend call, so single-device runs of a
    ``lookup_impl="sharded"`` config are exact no-ops.  The base accumulates
    per row independently, so a row's decoded value is invariant to which
    shard holds it — the 1-shard and N-shard runs agree bitwise.
    """

    name = "sharded"
    capabilities = BackendCapabilities(grad=True, fused=False)

    def __init__(self, base: Optional[object] = None, axis: Optional[str] = None,
                 mesh=None, interpret: bool = False,
                 policy: Optional[MixedPrecisionPolicy] = None):
        if base is None:
            base = "pallas" if jax.default_backend() == "tpu" else "onehot"
        _check_collective_base("sharded", base)
        self.base = get_backend(base, interpret=interpret, policy=policy)
        self.policy = self.base.policy
        self.axis = axis
        self.mesh = mesh
        self.preferred_pad = self.base.preferred_pad

    def dtype_contract(self) -> Dict[str, str]:
        contract = dict(self.base.dtype_contract(), backend=self.name)
        contract["collective_reduce"] = "float32 (psum of codebook/w0 grads)"
        return contract

    def feature_dim(self, codebooks) -> int:
        return self.base.feature_dim(codebooks)

    def _mesh_axis(self):
        return _active_mesh_axis(self.mesh, self.axis)

    def decode(self, codes, codebooks, w0=None):
        mesh, axis = self._mesh_axis()
        k = mesh.shape[axis] if mesh is not None else 1
        if k <= 1:
            return self.base.decode(codes, codebooks, w0)
        B = codes.shape[0]
        B_pad = _round_up(B, k)
        if B_pad != B:
            _warn_once(
                f"sharded-pad-b-{B}-{k}",
                f"sharded decode: padding batch {B} -> {B_pad} to split over "
                f"{k} shards; pad frontiers to a multiple of the shard count "
                f"(e.g. frontier_cap) to avoid the copy")
            codes = jnp.pad(codes, ((0, B_pad - B), (0, 0)))
        if w0 is None:
            # keep one shard_map signature: multiplying by exactly 1.0 is a
            # bitwise no-op, and the dummy's cotangent is simply discarded
            w0 = jnp.ones((self.base.feature_dim(codebooks),), jnp.float32)
        out = _sharded_decode(self.base, mesh, axis, codes, codebooks, w0)
        return out[:B]


# ---------------------------------------------------------------------------
# owner-computes (cross-shard dedup) decode
# ---------------------------------------------------------------------------

def _owner_decode(base: DecodeBackend, mesh, axis: str,
                  codes: Array, codebooks, w0: Array, plan) -> Array:
    """Owner-computes cross-shard frontier decode under ``shard_map``.

    Layout (all static, from the host-built ``OwnerPlan``): each shard's
    local frontier block has ``cap`` rows; requests are bucketed by
    ``owner = id % n`` into ``owner_cap`` slots per (requester, owner) pair.

        requester s: send[o, k]  = codes[req_rows[s, o, k]]      (gather)
                     ── all_to_all ─▶
        owner o:     owned[j]    = recv.flat[owned_src[o, j]]    (dedup)
                     dec         = base.decode(owned)            (ONCE per id)
                     ret[s, k]   = dec[ret_idx[o, s, k]]         (fan back out)
                     ── all_to_all ─▶
        requester s: out[req_rows[s, o, k]] = back[o, k]         (scatter)

    The forward ``all_gather``s the scattered blocks so the post-decode
    combine sees the full batch (same contract as the ``sharded`` backend).
    The custom VJP routes cotangents back through the same permutation:
    each requester slices its block of the (replicated) cotangent, sends it
    through the reverse exchange, and the owner scatter-*adds* the
    per-requester contributions onto its owned rows — so every decoded row's
    cotangent is accumulated exactly once, on its owner, before one
    ``base.decode`` VJP per owner produces disjoint codebook partials (the
    closing ``psum`` only sums those disjoint partials into the replicated
    codebook gradient; no duplicate row is ever double-counted)."""
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import all_to_all

    n = int(plan.req_rows.shape[0])
    oc = int(plan.req_rows.shape[2])
    cap = codes.shape[0] // n
    d = base.feature_dim(codebooks)
    ou = int(plan.owned_src.shape[1])
    plan_specs = (P(axis, None, None), P(axis, None), P(axis, None, None))
    cb_specs = _replicated_specs(codebooks)

    def _owned_codes(codes_l, rr, os_l):
        """Requester-side gather + all_to_all + owner-side dedup gather."""
        send = codes_l[jnp.clip(rr, 0, cap - 1)]            # (n, oc, m)
        recv = all_to_all(send, axis)                       # (n, oc, m)
        return recv.reshape(n * oc, -1)[os_l]               # (ou, m)

    @jax.custom_vjp
    def decode(codes, req_rows, owned_src, ret_idx, cb, w0):
        def local(codes_l, rr_l, os_l, ri_l, cb_, w0_):
            rr = rr_l[0]
            dec = base.decode(_owned_codes(codes_l, rr, os_l[0]), cb_, w0_)
            back = all_to_all(dec[ri_l[0]], axis)           # (n, oc, d)
            out_l = jnp.zeros((cap, d), dec.dtype).at[rr.reshape(-1)].set(
                back.reshape(-1, d), mode="drop")           # sentinel cap drops
            return jax.lax.all_gather(out_l, axis, axis=0, tiled=True)
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis, None),) + plan_specs + (cb_specs, P(None)),
            out_specs=P(None, None), check_vma=False)(
                codes, req_rows, owned_src, ret_idx, cb, w0)

    def fwd(codes, req_rows, owned_src, ret_idx, cb, w0):
        out = decode(codes, req_rows, owned_src, ret_idx, cb, w0)
        return out, (codes, req_rows, owned_src, ret_idx, cb, w0)

    def bwd(res, g):
        codes, req_rows, owned_src, ret_idx, cb, w0 = res

        def local(codes_l, rr_l, os_l, ri_l, g_full, cb_, w0_):
            rr = rr_l[0]
            owned = _owned_codes(codes_l, rr, os_l[0])
            s = jax.lax.axis_index(axis)
            g_blk = jax.lax.dynamic_slice_in_dim(g_full, s * cap, cap, 0)
            g_send = (g_blk[jnp.clip(rr, 0, cap - 1)]
                      * (rr < cap)[..., None].astype(g_full.dtype))
            g_recv = all_to_all(g_send, axis)               # (n, oc, d)
            # reduce_dtype contract: the per-requester scatter-add onto the
            # owned rows accumulates in f32
            ghat = jnp.zeros((ou, d), jnp.float32).at[
                ri_l[0].reshape(-1)].add(
                    g_recv.reshape(-1, d).astype(jnp.float32))
            _, vjp = jax.vjp(lambda c, sc: base.decode(owned, c, sc), cb_, w0_)
            gcb, gw0 = vjp(ghat.astype(g_full.dtype))
            gcb = _psum_f32(gcb, cb_, axis)
            gw0 = jax.lax.psum(gw0.astype(jnp.float32), axis).astype(w0_.dtype)
            return gcb, gw0

        gcb, gw0 = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis, None),) + plan_specs
            + (P(None, None), cb_specs, P(None)),
            out_specs=(cb_specs, P(None)), check_vma=False)(
                codes, req_rows, owned_src, ret_idx, g, cb, w0)
        return None, None, None, None, gcb, gw0   # ints: no gradient

    decode.defvjp(fwd, bwd)
    return decode(codes, plan.req_rows, plan.owned_src, plan.ret_idx,
                  codebooks, w0)


class OwnerBackend(DecodeBackend):
    """Owner-computes cross-shard frontier decode (ISSUE 5).

    The ``sharded`` backend decodes each shard's frontier block locally, so
    a hub node appearing in k shards' frontiers is decoded k times.  This
    backend hash-partitions rows by ``owner = node_id % n_shards``: each
    shard ``all_to_all``s its requests to the owning shard, the owner
    decodes every distinct id it owns exactly **once** (the cross-shard
    dedup), and a second ``all_to_all`` returns the embeddings.  The
    routing (a static-capacity ``OwnerPlan``) is built host-side in the
    batch source's prefetch thread, so the jitted step sees fixed shapes.

    Without a plan — or without a multi-device mesh, or when the plan's
    shard count doesn't match the mesh — the call degrades to the
    row-partitioned ``sharded`` decode of the same base backend (identical
    values, no dedup), so a ``lookup_impl="owner"`` config runs
    single-device tests unchanged and overflown plans fall back loudly
    upstream without ever truncating rows.
    """

    name = "owner"
    capabilities = BackendCapabilities(grad=True, fused=False)

    def __init__(self, base: Optional[object] = None, axis: Optional[str] = None,
                 mesh=None, interpret: bool = False,
                 policy: Optional[MixedPrecisionPolicy] = None):
        if base is None:
            base = "pallas" if jax.default_backend() == "tpu" else "onehot"
        _check_collective_base("owner", base)
        self.base = get_backend(base, interpret=interpret, policy=policy)
        self.policy = self.base.policy
        self.axis = axis
        self.mesh = mesh
        self.preferred_pad = self.base.preferred_pad
        # plan-less fallback: the row-partitioned sharded decode (values are
        # identical — rows just decode once per holding shard, not per owner)
        self._fallback = ShardedBackend(self.base, axis=axis, mesh=mesh)

    def dtype_contract(self) -> Dict[str, str]:
        contract = dict(self.base.dtype_contract(), backend=self.name)
        contract["collective_reduce"] = (
            "float32 (cotangent scatter-add on owned rows + grad psum)")
        return contract

    def feature_dim(self, codebooks) -> int:
        return self.base.feature_dim(codebooks)

    def decode(self, codes, codebooks, w0=None):
        return self._fallback.decode(codes, codebooks, w0)

    def decode_frontier(self, codes, codebooks, w0=None, *, plan=None):
        mesh, axis = _active_mesh_axis(self.mesh, self.axis)
        k = mesh.shape[axis] if mesh is not None else 1
        if plan is None or k <= 1:
            return self.decode(codes, codebooks, w0)
        n = int(plan.req_rows.shape[0])
        if n != k or codes.shape[0] % n:
            _warn_once(
                f"owner-plan-mismatch-{n}-{k}-{codes.shape[0]}",
                f"owner decode: plan built for {n} shards / "
                f"{codes.shape[0]} rows does not match the {k}-way mesh; "
                f"falling back to the row-partitioned sharded decode")
            return self.decode(codes, codebooks, w0)
        if w0 is None:
            # same trick as ShardedBackend: one shard_map signature, and
            # multiplying by exactly 1.0 is a bitwise no-op
            w0 = jnp.ones((self.base.feature_dim(codebooks),), jnp.float32)
        return _owner_decode(self.base, mesh, axis, codes, codebooks, w0, plan)


# ---------------------------------------------------------------------------
# compression families (ROADMAP item 4)
# ---------------------------------------------------------------------------

# Registry names that select an alternate *compression family* (how the
# embedding table is parameterized) rather than an execution strategy.  A
# ``lookup_impl`` selects at most one; ``family_of`` finds it anywhere in
# the ":"-separated spelling, so "owner:tt" and "hashemb:gather" both work.
FAMILY_BACKENDS: Tuple[str, ...] = ("hashemb", "tt")


def family_of(lookup_impl: Optional[str]) -> str:
    """Compression family selected by a ``lookup_impl`` string: ``"hashemb"``
    / ``"tt"`` when that name appears in any ":"-separated part, else
    ``"paper"`` (the source paper's bit-code hashing — every pre-existing
    spelling, including ``auto`` and the collective wrappers)."""
    for part in (lookup_impl or "auto").split(":"):
        if part in FAMILY_BACKENDS:
            return part
    return "paper"


class HashEmbBackend(DecodeBackend):
    """Position-based hash embeddings (arXiv:2109.00101) as a decode family.

    Parameterization: m shared pools ``(m, c, d_c)`` plus learned
    per-position weights ``wpos (m, d_c)``; entity id ``i`` contributes
    ``sum_j wpos[j] * pools[j, h_j(i)]`` where ``h_j`` are m independent
    hash functions (``core.codes.position_codes`` — recomputed from the id
    at lookup time, so NO per-entity ``codes_buf`` exists and id-side memory
    is zero).  ``apply_decoder`` folds ``wpos`` into the pools before the
    call (``sum_j (wpos[j]*P[j])[h_j(i)] == sum_j wpos[j]*P[j][h_j(i)]``,
    exact in f32 and differentiable to both factors), so what reaches this
    backend is a standard ``(m, c, d_c)`` codebook gather — delegated
    verbatim to a base backend (gather/onehot/pallas, incl. int8/bf16
    policies).  ``"hashemb:gather"`` pins the base; ``"owner:hashemb"`` /
    ``"sharded:hashemb"`` compose with the collectives unchanged."""

    name = "hashemb"
    capabilities = BackendCapabilities(grad=True, fused=False)

    def __init__(self, base: Optional[object] = None, interpret: bool = False,
                 policy: Optional[MixedPrecisionPolicy] = None):
        if base is None:
            base = "pallas" if jax.default_backend() == "tpu" else "onehot"
        _check_collective_base("hashemb", base)
        if isinstance(base, str) and base.split(":")[0] in FAMILY_BACKENDS:
            raise ValueError(
                f"hashemb backend cannot wrap another family (base={base!r})")
        self.base = get_backend(base, interpret=interpret, policy=policy)
        self.policy = self.base.policy
        self.preferred_pad = self.base.preferred_pad

    def dtype_contract(self) -> Dict[str, str]:
        contract = dict(self.base.dtype_contract(), backend=self.name)
        contract["family"] = "hashemb (pools + per-position weights)"
        return contract

    def feature_dim(self, codebooks) -> int:
        return self.base.feature_dim(codebooks)

    def decode(self, codes, codebooks, w0=None):
        return self.base.decode(codes, codebooks, w0)


def tt_factor_pair(n: int) -> Tuple[int, int]:
    """Most-balanced factorization ``n = a * b`` with ``a <= b`` (a scans
    down from isqrt).  Used for both the code split ``c = c1*c2`` and the
    feature split ``d_c = d1*d2`` of the ``tt`` family."""
    if n < 1:
        raise ValueError(f"cannot factor {n}")
    a = int(np.sqrt(n))
    while n % a:
        a -= 1
    return a, n // a


def tt_materialize(g0: Array, g1: Array) -> Array:
    """Contract a TT core pair back into the dense ``(m, c, d_c)`` codebook
    it factorizes — the oracle for parity tests and the ``trainable_params``
    accounting, never used on the decode hot path.

    ``g0 (m, c1, d1, r)``, ``g1 (m, c2, r, d2)`` →
    ``cb[j, x1*c2 + x2, u*d2 + v] = sum_r g0[j, x1, u, r] * g1[j, x2, r, v]``
    """
    m, c1, d1, r = g0.shape
    _, c2, _, d2 = g1.shape
    full = jnp.einsum("jxur,jyrv->jxyuv",
                      g0.astype(jnp.float32), g1.astype(jnp.float32))
    return full.reshape(m, c1 * c2, d1 * d2)


class TTBackend(DecodeBackend):
    """Tensor-train factorized codebooks (Nimble GNN, arXiv:2206.10581).

    The dense ``(m, c, d_c)`` codebook is stored as two TT cores
    ``g0 (m, c1, d1, r)`` / ``g1 (m, c2, r, d2)`` with ``c = c1*c2`` and
    ``d_c = d1*d2`` (balanced splits from ``tt_factor_pair``), cutting
    codebook memory from ``m*c*d_c`` to ``m*(c1*d1 + c2*d2)*r`` floats.
    ``decode`` fuses the rank-r contraction into the lookup: each code
    splits as ``x1 = code // c2``, ``x2 = code % c2``, both cores' rows are
    gathered and ONE f32 einsum sums the position contributions — the dense
    codebook is never materialized (``tt_materialize`` exists only as the
    parity/accounting oracle).  ``codebooks`` is therefore the pytree
    ``(g0, g1)``; the collective wrappers handle that via their pytree
    specs, so ``"owner:tt"`` / ``"sharded:tt"`` compose unchanged."""

    name = "tt"
    capabilities = BackendCapabilities(grad=True, fused=False)
    preferred_pad = _SUBLANE

    def __init__(self, policy: Optional[MixedPrecisionPolicy] = None):
        self.policy = policy or DEFAULT_POLICY

    def dtype_contract(self) -> Dict[str, str]:
        contract = super().dtype_contract()
        contract["family"] = "tt (rank-r core pair, contraction fused)"
        contract["accumulate"] = "float32 (core einsum + position sum)"
        return contract

    def feature_dim(self, codebooks) -> int:
        g0, g1 = codebooks
        return int(g0.shape[2]) * int(g1.shape[3])

    def _quantized(self, g0, g1):
        """absmax-int8 per (codebook, code row), like the dense path: each
        core reshapes its per-code row to one vector, rides the same
        straight-through ``quantize_dequantize``, and reshapes back."""
        from repro.kernels.hash_decode import ops as hd_ops
        m, c1, d1, r = g0.shape
        _, c2, _, d2 = g1.shape
        g0 = hd_ops.quantize_dequantize(
            g0.reshape(m, c1, d1 * r)).reshape(m, c1, d1, r)
        g1 = hd_ops.quantize_dequantize(
            g1.reshape(m, c2, r * d2)).reshape(m, c2, r, d2)
        return g0, g1

    def decode(self, codes, codebooks, w0=None):
        codebooks, w0 = self._prep(codebooks, w0)
        if self.policy.quantize == "int8":
            codebooks = self._quantized(*codebooks)
        g0, g1 = codebooks
        m, c1, d1, r = g0.shape
        _, c2, _, d2 = g1.shape
        x1 = codes // c2                                   # (B, m)
        x2 = codes % c2
        j = jnp.arange(m, dtype=codes.dtype)[None, :]      # (1, m)
        a0 = g0[j, x1].astype(jnp.float32)                 # (B, m, d1, r)
        a1 = g1[j, x2].astype(jnp.float32)                 # (B, m, r, d2)
        # one contraction: rank-r core product AND the sum over the m
        # positions, all accumulated in f32 (the reduce_dtype contract)
        out = jnp.einsum("bjur,bjrv->buv", a0, a1).reshape(-1, d1 * d2)
        if w0 is not None:
            out = out * w0.astype(jnp.float32)[None, :]
        return out


# ---------------------------------------------------------------------------
# registry / selection
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[..., DecodeBackend]] = {}


def register_backend(name: str, factory: Callable[..., DecodeBackend]) -> None:
    """Register a backend factory; ``factory(**opts) -> DecodeBackend``.
    Re-registering a name overrides it (tests swap in instrumented fakes)."""
    _REGISTRY[name] = factory


register_backend("gather", GatherBackend)
register_backend("onehot", OnehotBackend)
register_backend("pallas", PallasBackend)
register_backend("sharded", ShardedBackend)
register_backend("owner", OwnerBackend)
register_backend("hashemb", HashEmbBackend)
register_backend("tt", TTBackend)

# ``auto`` prefers the owner-computes decode over the plain sharded decode
# when the workload's measured duplication (frontier_rows / unique_rows, the
# per-device decode work over the mean per-shard unique count — what
# BENCH_shard.json reports) exceeds this: past 2x, the owner exchange
# reclaims more decode rows than its two all_to_alls cost, and the default
# owner_unique_cap = cap/2 sizing (graph.sampler.default_owner_caps) is
# guaranteed adequate in expectation by the same inequality.
OWNER_DUP_THRESHOLD = 2.0


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def rederive_owner_caps(frontier_cap: int, n_shards: int,
                        explicit: Tuple[Optional[int], Optional[int]] = (None, None),
                        ) -> Tuple[Optional[int], Optional[int]]:
    """Owner-exchange capacities for a (possibly rescaled) shard count.

    The ``(owner_cap, owner_unique_cap)`` sizing depends on ``n_shards``
    (request buckets shrink as shards multiply), so an elastic rescale must
    not carry the old run's caps over verbatim.  Policy: if the caller never
    pinned caps explicitly (both ``None``), keep them derived — return
    ``(None, None)`` and let the runtime size them per-plan; if either was
    pinned, re-derive both from ``default_owner_caps`` at the *new* shard
    count, which preserves the cap/2 adequacy argument documented there."""
    if explicit[0] is None and explicit[1] is None:
        return (None, None)
    from repro.graph.sampler import default_owner_caps
    return default_owner_caps(int(frontier_cap), int(n_shards))


def resolve_auto(duplication: Optional[float] = None) -> str:
    """``auto`` resolution: under a mesh whose data axis is actually split,
    the owner-computes decode when the measured frontier duplication
    justifies the exchange (``duplication > OWNER_DUP_THRESHOLD``) and the
    plain sharded decode otherwise; single-device, the fused kernel on TPU
    runtimes and the MXU-friendly XLA formulation everywhere else."""
    from repro.parallel.sharding import data_axis_size
    if data_axis_size() > 1:
        if duplication is not None and duplication > OWNER_DUP_THRESHOLD:
            return "owner"
        return "sharded"
    return "pallas" if jax.default_backend() == "tpu" else "onehot"


def get_backend(spec, *, interpret: bool = False,
                duplication: Optional[float] = None,
                policy: Optional[MixedPrecisionPolicy] = None) -> DecodeBackend:
    """Resolve a backend from a config string (or pass an instance through).

    ``auto`` picks a collective decode under a multi-device mesh (``owner``
    when the measured ``duplication`` beats ``OWNER_DUP_THRESHOLD``, else
    ``sharded``), the fused kernel on TPU runtimes and the MXU-friendly XLA
    formulation elsewhere.  ``sharded`` / ``owner`` / ``hashemb`` accept an
    optional base-backend suffix — ``"owner:gather"`` decodes owner-local
    through the gather oracle (bitwise-stable row accumulation),
    ``"hashemb:gather"`` pins the pool gather.  ``interpret`` affects
    ``pallas`` (directly or as a collective base).  ``policy`` sets the
    backend's ``MixedPrecisionPolicy``; it is only forwarded when given, so
    test-registered factories without the kwarg keep working."""
    if isinstance(spec, DecodeBackend):
        return spec
    name = spec or "auto"
    if name == "auto":
        name = resolve_auto(duplication)
    name, _, option = name.partition(":")
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown decode backend {name!r}; known: {available_backends()}")
    kwargs = {} if policy is None else {"policy": policy}

    def build(factory, **fixed):
        try:
            return factory(**fixed, **kwargs)
        except TypeError:
            if not kwargs:
                raise
            # legacy factory without the policy kwarg (e.g. a test-registered
            # fake): construct it plain and attach the policy as an attribute
            be = factory(**fixed)
            be.policy = policy
            return be

    if name in ("sharded", "owner", "hashemb"):
        return build(_REGISTRY[name], base=option or None, interpret=interpret)
    if option:
        raise ValueError(
            f"decode backend {name!r} takes no ':{option}' option "
            f"(only 'sharded:<base>' / 'owner:<base>' / 'hashemb:<base>' do)")
    if name == "pallas":
        return build(_REGISTRY[name], interpret=interpret)
    return build(_REGISTRY[name])


# ---------------------------------------------------------------------------
# hot-node cache
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CacheState:
    """Functional state of the hot-node decode cache (a pytree: it lives in
    the train state, flows through jit, and checkpoints like any buffer).

    ``node_ids``   (C,) int32 entity id per slot (-1 = empty)
    ``values``     (C, d) f32 cached decoded embeddings
    ``version``    (C,) int32 codebook version each entry was decoded at
    ``last_used``  (C,) int32 LRU clock of last access
    ``version_counter`` () int32 current codebook version (bumped per
                   optimizer update)
    ``clock``      () int32 access counter driving LRU order
    ``hits`` / ``misses`` () int32 cumulative accounting
    """

    node_ids: Array
    values: Array
    version: Array
    last_used: Array
    version_counter: Array
    clock: Array
    hits: Array
    misses: Array

    def tree_flatten(self):
        return (self.node_ids, self.values, self.version, self.last_used,
                self.version_counter, self.clock, self.hits, self.misses), None

    @classmethod
    def tree_unflatten(cls, _aux, leaves):
        return cls(*leaves)

    @classmethod
    def create(cls, capacity: int, d: int, dtype=jnp.float32) -> "CacheState":
        i32 = jnp.int32
        return cls(
            node_ids=jnp.full((capacity,), -1, i32),
            values=jnp.zeros((capacity, d), dtype),
            version=jnp.full((capacity,), jnp.iinfo(i32).min // 2, i32),
            last_used=jnp.full((capacity,), jnp.iinfo(i32).min // 2, i32),
            version_counter=jnp.zeros((), i32),
            clock=jnp.zeros((), i32),
            hits=jnp.zeros((), i32),
            misses=jnp.zeros((), i32),
        )

    @property
    def capacity(self) -> int:
        return self.node_ids.shape[0]


class CachedDecodeBackend:
    """LRU cache of decoded embeddings keyed by entity id, wrapping any base
    decode path.

    ``lookup(state, ids, decode_fn)`` serves each id from the cache when its
    entry is fresh enough (``version_counter - entry_version <= staleness``)
    and re-decodes otherwise; re-decoded rows are written back (LRU
    eviction), hit rows only refresh their LRU stamp.  Gradients flow
    through ``decode_fn`` for misses only — cached rows are constants from
    an earlier version, which is exactly the staleness trade.

    Ids within one lookup should be unique (the frontier decode guarantees
    it — pass ``valid`` to mask its padding rows); duplicate miss ids burn
    duplicate slots but reads stay correct.  At ``staleness=0`` an entry is
    only fresh within the version it was written at, so with one lookup per
    optimizer step every access re-decodes and training is bit-identical to
    the uncached path.
    """

    def __init__(self, staleness: int = 0):
        self.staleness = int(staleness)

    def init_state(self, capacity: int, d: int, dtype=jnp.float32) -> CacheState:
        return CacheState.create(capacity, d, dtype)

    @staticmethod
    def dtype_contract(base: Optional[DecodeBackend] = None) -> Dict[str, str]:
        """Cache-layer dtype contract: misses inherit the base backend's
        contract end to end; hits are served from ``CacheState.values``
        (stored in the model's compute dtype) — so a cached hit adds one
        compute-dtype round-trip on top of the base drift bound and nothing
        else.  Hit/miss select and all bookkeeping are dtype-free."""
        contract = {
            "backend": "cached",
            "storage": "CacheState.values in compute dtype (hits); "
                       "base backend storage (misses)",
            "compute": "base backend",
            "accumulate": "float32 (base backend)",
            "output": "float32",
        }
        if base is not None:
            contract["base"] = base.dtype_contract()["backend"]
        return contract

    def lookup(self, state: CacheState, ids: Array,
               decode_fn: Callable[[Array], Array],
               valid: Optional[Array] = None):
        """ids (U,) int32 -> ((U, d) embeddings, new CacheState).

        ``valid`` (U,) bool masks rows out of the cache entirely (they still
        decode, but never hit, never write, and don't count in the hit/miss
        accounting) — used for the frontier's jit-shape padding rows, which
        are duplicates of row 0."""
        C = state.capacity
        U = ids.shape[0]
        eq = ids[:, None] == state.node_ids[None, :]          # (U, C)
        found = eq.any(axis=1)
        if valid is not None:
            found = found & valid
        slot = jnp.argmax(eq, axis=1)                         # valid iff found
        age = state.version_counter - state.version[slot]
        hit = found & (age <= self.staleness)

        fresh = decode_fn(ids)                                # (U, d)
        out = jnp.where(hit[:, None], state.values[slot].astype(fresh.dtype),
                        fresh)

        # ---- state update (all scatters masked via index C + mode="drop")
        clock = state.clock + 1
        n_valid = (jnp.int32(U) if valid is None
                   else valid.sum(dtype=jnp.int32))
        n_hit = hit.sum(dtype=jnp.int32)

        # hits only refresh their LRU stamp
        hidx = jnp.where(hit, slot, C)
        last_used = state.last_used.at[hidx].set(clock, mode="drop")

        # misses write back: stale-but-present entries refresh in place,
        # absent ids take the least-recently-used unprotected slots.  Only
        # the first n_free absent misses get a slot — ranks past that would
        # reach into the protected suffix of evict_order and collide with a
        # found row's in-place refresh (two ids scattering to one slot).
        protected = jnp.zeros((C,), bool).at[jnp.where(found, slot, C)].set(
            True, mode="drop")
        n_free = C - protected.sum(dtype=jnp.int32)
        evict_order = jnp.argsort(
            jnp.where(protected, jnp.iinfo(jnp.int32).max, last_used))
        needs_slot = ~found
        if valid is not None:
            needs_slot = needs_slot & valid
        rank = jnp.cumsum(needs_slot.astype(jnp.int32)) - 1   # (U,)
        new_slot = evict_order[jnp.clip(rank, 0, C - 1)]
        write = (~hit) & (found | (needs_slot & (rank < n_free)))
        widx = jnp.where(write, jnp.where(found, slot, new_slot), C)

        wvals = jax.lax.stop_gradient(fresh).astype(state.values.dtype)
        new_state = CacheState(
            node_ids=state.node_ids.at[widx].set(ids, mode="drop"),
            values=state.values.at[widx].set(wvals, mode="drop"),
            version=state.version.at[widx].set(state.version_counter,
                                               mode="drop"),
            last_used=last_used.at[widx].set(clock, mode="drop"),
            version_counter=state.version_counter,
            clock=clock,
            hits=state.hits + n_hit,
            misses=state.misses + (n_valid - n_hit),
        )
        return out, new_state

    # -- miss-only decode (ROADMAP "Next": only misses enter the decoder) --
    @staticmethod
    def plan_missonly(cached_ids, ids, valid=None):
        """Host-side miss partition for ``lookup_missonly``.

        ``cached_ids`` is the host view of the cache's *fresh* entries
        (``np.asarray(state.node_ids)`` when nothing can be stale, e.g. at
        serving time where the version counter never moves; negative ids —
        empty slots — are ignored).  Returns ``(perm, n_miss)``: a stable
        permutation of ``ids`` placing every row that will miss (valid and
        not cached) first, and the count of such rows.  The caller permutes
        the frontier with ``perm`` (and its index maps with the inverse)
        and hands the decoder only a padded prefix."""
        import numpy as np
        ids = np.asarray(ids)
        if valid is None:
            valid = np.ones(ids.shape[0], bool)
        cached_ids = np.asarray(cached_ids)
        cached_ids = cached_ids[cached_ids >= 0]
        miss = np.asarray(valid, bool) & ~np.isin(ids, cached_ids)
        perm = np.argsort(~miss, kind="stable").astype(np.int32)
        return perm, int(miss.sum())

    def lookup_missonly(self, state: CacheState, ids: Array,
                        decode_fn: Callable[[Array], Array],
                        n_decode: int, valid: Optional[Array] = None):
        """Miss-only twin of ``lookup``: ``decode_fn`` runs ONLY on the
        first ``n_decode`` rows (a static int — shape-bucketed jit), so the
        decoder pays for misses instead of the whole frontier.

        Contract (kept by ``plan_missonly``): the caller permuted ``ids``
        miss-first, so every valid row at position >= ``n_decode`` is a
        fresh cache hit.  Prefix rows that turn out to be hits anyway (the
        miss-count padding) are still served from the cache, which keeps
        the output bitwise identical to ``lookup``; a *miss* past the
        prefix would read zeros — that is a planner bug, not a decode
        fallback.  State updates (write-back, LRU, accounting) are
        restricted to the decoded prefix."""
        C = state.capacity
        U = ids.shape[0]
        d = state.values.shape[1]
        eq = ids[:, None] == state.node_ids[None, :]          # (U, C)
        found = eq.any(axis=1)
        if valid is not None:
            found = found & valid
        slot = jnp.argmax(eq, axis=1)
        age = state.version_counter - state.version[slot]
        hit = found & (age <= self.staleness)

        if n_decode > 0:
            fresh_prefix = decode_fn(ids[:n_decode])          # (n_decode, d)
            fresh = jnp.zeros((U, d), fresh_prefix.dtype)
            fresh = fresh.at[:n_decode].set(fresh_prefix)
        else:
            fresh = jnp.zeros((U, d), state.values.dtype)
        out = jnp.where(hit[:, None], state.values[slot].astype(fresh.dtype),
                        fresh)

        # ---- state update: identical to ``lookup`` but writes only rows
        # the decoder actually produced (the prefix)
        decoded = jnp.arange(U, dtype=jnp.int32) < n_decode
        clock = state.clock + 1
        n_valid = (jnp.int32(U) if valid is None
                   else valid.sum(dtype=jnp.int32))
        n_hit = hit.sum(dtype=jnp.int32)

        hidx = jnp.where(hit, slot, C)
        last_used = state.last_used.at[hidx].set(clock, mode="drop")

        protected = jnp.zeros((C,), bool).at[jnp.where(found, slot, C)].set(
            True, mode="drop")
        n_free = C - protected.sum(dtype=jnp.int32)
        evict_order = jnp.argsort(
            jnp.where(protected, jnp.iinfo(jnp.int32).max, last_used))
        needs_slot = ~found & decoded
        if valid is not None:
            needs_slot = needs_slot & valid
        rank = jnp.cumsum(needs_slot.astype(jnp.int32)) - 1
        new_slot = evict_order[jnp.clip(rank, 0, C - 1)]
        write = (~hit) & decoded & (found | (needs_slot & (rank < n_free)))
        widx = jnp.where(write, jnp.where(found, slot, new_slot), C)

        wvals = jax.lax.stop_gradient(fresh).astype(state.values.dtype)
        new_state = CacheState(
            node_ids=state.node_ids.at[widx].set(ids, mode="drop"),
            values=state.values.at[widx].set(wvals, mode="drop"),
            version=state.version.at[widx].set(state.version_counter,
                                               mode="drop"),
            last_used=last_used.at[widx].set(clock, mode="drop"),
            version_counter=state.version_counter,
            clock=clock,
            hits=state.hits + n_hit,
            misses=state.misses + (n_valid - n_hit),
        )
        return out, new_state

    @staticmethod
    def bump_version(state: CacheState) -> CacheState:
        """Codebook/decoder update notification — call once per optimizer
        step that touches decoder parameters."""
        return dataclasses.replace(
            state, version_counter=state.version_counter + 1)


class HostCacheShadow:
    """Host-side numpy replica of the ``CacheState`` *bookkeeping* (never
    the values), used to plan miss-only decode for **training**.

    The training miss partition (``graph.engine.MissPlanningSource``) must
    know, while batch k+1 is still on the producer thread, which frontier
    ids will be fresh cache hits when the jitted step consumes it — i.e.
    after batch k's write-backs and version bump have landed on device.
    The cache bookkeeping (``node_ids`` / ``version`` / ``last_used`` /
    counters) depends only on the ``(ids, valid, n_decode)`` sequence,
    never on decoded values, so a host replica fed the same per-step inputs
    tracks the device cache *exactly*: ``update`` mirrors
    ``CachedDecodeBackend.lookup_missonly``'s state update line for line
    (same stable argsort, same protected / rank < n_free slot assignment)
    followed by the train step's ``bump_version``.

    Prediction safety is one-sided.  A predicted miss that turns out to hit
    is harmless — ``lookup_missonly`` serves prefix hits from the cache; a
    predicted hit that actually misses reads zeros.  ``clear()`` therefore
    resets to the empty shadow (plans *everything* as a miss: slower, never
    wrong), and ``sync_from_cache_state`` re-anchors an out-of-sync shadow
    to a restored device cache on checkpoint resume.
    """

    _EMPTY = np.iinfo(np.int32).min // 2   # matches CacheState.create

    def __init__(self, capacity: int, staleness: int = 0):
        self.capacity = int(capacity)
        self.staleness = int(staleness)
        self.clear()

    def clear(self) -> None:
        C = self.capacity
        self.node_ids = np.full((C,), -1, np.int32)
        self.version = np.full((C,), self._EMPTY, np.int32)
        self.last_used = np.full((C,), self._EMPTY, np.int32)
        self.version_counter = 0
        self.clock = 0

    # -- (de)serialisation ----------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """JSON-friendly copy (checkpointable alongside the source state)."""
        return {
            "capacity": self.capacity, "staleness": self.staleness,
            "node_ids": self.node_ids.tolist(),
            "version": self.version.tolist(),
            "last_used": self.last_used.tolist(),
            "version_counter": int(self.version_counter),
            "clock": int(self.clock),
        }

    def restore(self, snap: Dict[str, Any]) -> None:
        if int(snap["capacity"]) != self.capacity:
            raise ValueError(
                f"shadow snapshot capacity {snap['capacity']} != {self.capacity}")
        self.staleness = int(snap["staleness"])
        self.node_ids = np.asarray(snap["node_ids"], np.int32).copy()
        self.version = np.asarray(snap["version"], np.int32).copy()
        self.last_used = np.asarray(snap["last_used"], np.int32).copy()
        self.version_counter = int(snap["version_counter"])
        self.clock = int(snap["clock"])

    def sync_from_cache_state(self, state: CacheState) -> None:
        """Re-anchor to a device cache (exact: same fields, host copies)."""
        self.node_ids = np.asarray(state.node_ids, np.int32).copy()
        self.version = np.asarray(state.version, np.int32).copy()
        self.last_used = np.asarray(state.last_used, np.int32).copy()
        self.version_counter = int(state.version_counter)
        self.clock = int(state.clock)

    # -- planning --------------------------------------------------------
    def fresh_ids(self) -> np.ndarray:
        """Ids whose cached entry will still be within the staleness budget
        at the next lookup (the shadow is post-bump, like the device)."""
        live = self.node_ids >= 0
        fresh = (self.version_counter - self.version) <= self.staleness
        return self.node_ids[live & fresh]

    def plan(self, ids: np.ndarray, valid: np.ndarray):
        """``(perm, n_miss)`` for the next batch — ``plan_missonly``
        against the *fresh* (not merely present) shadow entries."""
        return CachedDecodeBackend.plan_missonly(self.fresh_ids(), ids, valid)

    # -- state transition ------------------------------------------------
    def update(self, ids: np.ndarray, valid: np.ndarray, n_decode: int) -> None:
        """Replay one training step's cache transition: the bookkeeping of
        ``lookup_missonly(ids, ..., n_decode, valid)`` plus the optimizer
        ``bump_version``.  ``ids``/``valid`` must be the *permuted* arrays
        the device step will see."""
        C = self.capacity
        ids = np.asarray(ids, np.int32)
        valid = np.asarray(valid, bool)
        U = ids.shape[0]
        eq = ids[:, None] == self.node_ids[None, :]            # (U, C)
        found = eq.any(axis=1) & valid
        slot = eq.argmax(axis=1)
        age = self.version_counter - self.version[slot]
        hit = found & (age <= self.staleness)
        decoded = np.arange(U) < int(n_decode)

        self.clock += 1
        last_used = self.last_used.copy()
        last_used[slot[hit]] = self.clock                      # hit refresh

        protected = np.zeros((C,), bool)
        protected[slot[found]] = True
        n_free = C - int(protected.sum())
        # device argsort (jnp) is stable — kind="stable" keeps slot
        # assignment bit-identical through the INT32_MAX / empty-slot ties
        evict_order = np.argsort(
            np.where(protected, np.iinfo(np.int32).max, last_used),
            kind="stable")
        needs_slot = ~found & decoded & valid
        rank = np.cumsum(needs_slot) - 1
        new_slot = evict_order[np.clip(rank, 0, C - 1)]
        write = (~hit) & decoded & (found | (needs_slot & (rank < n_free)))
        widx = np.where(found, slot, new_slot)

        w = widx[write]
        self.node_ids[w] = ids[write]
        self.version[w] = self.version_counter
        last_used[w] = self.clock
        self.last_used = last_used
        self.version_counter += 1                              # bump_version
