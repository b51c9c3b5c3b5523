"""Smoke run of the main path on a TPU chip.

``GraphRuntime`` builds GraphSAGE with the paper's bit-code node embeddings
at the published widths (c=256, m=16, d_c=d_m=512, l=3, d_e=64, 2x128 SAGE,
fanout 15) decoded by the Pallas kernel, on a seeded 100k-node power-law
graph, then trains, evaluates and serves it through the same entry points a
user calls.  Each phase prints one line; any failure exits non-zero.  The
last line of a successful run is one JSON object naming the device.

    python chip_smoke.py                 # one chip, every phase
    python chip_smoke.py --chips 4       # four chips: 1-shard vs 4-shard
                                         # sharded:pallas vs owner:pallas only
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # CPU rehearsal
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --tiny --chips 4

There is no CPU fallback: without ``--tiny`` a platform other than TPU is an
error.  ``--tiny`` shrinks the graph and widths, runs Pallas in interpret
mode and skips only the platform and ``tpu_custom_call`` checks.
Everything is built from ``--seed``; nothing is fetched.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Dict, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

F32_EPS = 2.0 ** -23
# f32 rounding, for a loss: a few ulp of its magnitude
LOSS_RTOL = 8 * F32_EPS


@dataclasses.dataclass(frozen=True)
class Size:
    nodes: int
    classes: int
    batch: int
    fanout: int
    # one frontier shape for every step; None = the worst case
    # (graph.engine.default_frontier_cap).  53,248 clears the largest
    # unique frontier of batch 1024 at fanout 15 on the 100k-node graph
    # (50,032 over 30 sampled steps of seed 0).
    frontier_cap: Optional[int]
    eval_batch: int
    serve_sizes: Tuple[int, ...]
    widths: Dict[str, int] = dataclasses.field(default_factory=dict)


FULL = Size(nodes=100_000, classes=16, batch=1024, fanout=15,
            frontier_cap=53_248, eval_batch=1024,
            serve_sizes=(256, 131, 64, 17))
TINY = Size(nodes=2_000, classes=8, batch=64, fanout=5, frontier_cap=None,
            eval_batch=256, serve_sizes=(32, 21, 9, 4),
            widths=dict(c=16, m=8, d_c=128, d_m=128))


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {msg}")


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def build_spec(size: Size, seed: int):
    from repro.configs.paper_gnn import paper_gnn_config
    from repro.graph.engine import default_frontier_cap
    from repro.graph.runtime import GraphSource, RuntimeSpec
    from repro.optim import AdamWConfig
    fanouts = (size.fanout, size.fanout)
    cap = size.frontier_cap or default_frontier_cap(size.batch, fanouts, 256,
                                                    size.nodes)
    spec = RuntimeSpec(
        graph=GraphSource(kind="powerlaw", seed=seed, n_nodes=size.nodes,
                          n_classes=size.classes, avg_degree=10,
                          homophily=0.85),
        model=paper_gnn_config("sage", n_nodes=size.nodes,
                               n_classes=size.classes, fanout=size.fanout),
        optimizer=AdamWConfig(lr=1e-2, weight_decay=0.0),
        batch_size=size.batch, frontier_cap=cap, data_seed=seed,
        init_seed=seed, split_seed=seed, eval_batch=size.eval_batch)
    return spec.with_updates(lookup_impl="pallas", **size.widths)


def check_platform(tiny: bool, chips: int):
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if not tiny and d0.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found platform {d0.platform!r} "
                 f"({d0.device_kind}), not 'tpu'; there is no CPU fallback "
                 f"(--tiny is the CPU rehearsal)")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, JAX "
                 f"sees {len(devices)}")
    return d0


def peek_batch(rt):
    """The runtime's next batch, without consuming it: rewinds the
    prefetch iterator to its checkpointed position afterwards."""
    state = rt.data_iter.state_dict()
    batch = rt.data_iter.next_batch()
    rt.data_iter.load_state_dict(state)
    return batch


def decode_errors(rt, batch):
    """Max-abs error of the pallas and onehot decodes against the gather
    oracle on the batch's frontier rows, and the oracle's max-abs value."""
    import jax
    import jax.numpy as jnp
    from repro.core.backend import get_backend
    from repro.core.codes import unpack_codes
    ecfg = rt.cfg.embedding_config()
    emb = rt.params["embed"]
    packed = jnp.take(emb["codes_buf"], batch["frontier"].unique, axis=0)
    codes = unpack_codes(packed, ecfg.c, ecfg.m)
    cb = emb["decoder"]["codebooks"]
    ref = jax.jit(get_backend("gather").decode)(codes, cb)
    errs = {}
    for name in ("pallas", "onehot"):
        be = get_backend(name, interpret=rt.interpret)
        errs[name] = float(jnp.max(jnp.abs(jax.jit(be.decode)(codes, cb)
                                           - ref)))
    return errs, float(jnp.max(jnp.abs(ref)))


def memory(device) -> Dict[str, object]:
    stats = device.memory_stats() or {}
    return {k: stats.get(k, "not-reported")
            for k in ("bytes_in_use", "peak_bytes_in_use")}


def one_chip(size: Size, seed: int, tiny: bool) -> None:
    import jax
    import numpy as np
    from repro.graph.engine import GNNModel
    from repro.graph.runtime import GraphRuntime

    spec = build_spec(size, seed)
    t0 = time.perf_counter()
    graph = spec.graph.build()
    t1 = time.perf_counter()
    rt = GraphRuntime.from_spec(spec, graph=graph)
    t2 = time.perf_counter()
    d0 = jax.devices()[0]
    if not tiny:
        require(rt.interpret is False, "runtime resolved Pallas interpret mode")
    say("1/7 device", platform=d0.platform, kind=repr(d0.device_kind),
        count=jax.device_count(), interpret=rt.interpret,
        nodes=size.nodes, edges=rt.adj.nnz, graph_s=t1 - t0,
        runtime_s=t2 - t1)

    # -- 2: the train step holds the kernel, not the reference ----------
    batch = peek_batch(rt)
    t0 = time.perf_counter()
    text = rt.jitted_step.lower(rt.state, batch).compile().as_text()
    compile_s = time.perf_counter() - t0
    has_kernel = ('custom_call_target="tpu_custom_call"' in text
                  and "hash_decode" in text)
    if not tiny:
        require(has_kernel, "hash_decode tpu_custom_call missing from the step")
    say("2/7 kernel", hash_decode_tpu_custom_call=has_kernel,
        frontier_rows=batch["frontier"].unique.shape[0],
        compile_s=compile_s)

    # -- 3: train, against the gather oracle ----------------------------
    errs, scale = decode_errors(rt, batch)
    m = rt.cfg.embedding.m
    decode_tol = m * F32_EPS * scale
    res = rt.train(5)
    grt = GraphRuntime.from_spec(spec.with_updates(lookup_impl="gather"),
                                 graph=graph)
    gres = grt.train(5)
    grt.close()
    gaps = [abs(a - b) for a, b in zip(res.losses, gres.losses)]
    say("3/7 train", losses=res.losses, gather_losses=gres.losses,
        step0_gap=gaps[0], later_gaps=gaps[1:],
        step_s_2to5=res.step_times[1:],
        pallas_decode_err=errs["pallas"], onehot_decode_err=errs["onehot"],
        decode_max_abs=scale, decode_tol=decode_tol)
    require(all(np.isfinite(res.losses)), f"non-finite loss: {res.losses}")
    require(gaps[0] <= LOSS_RTOL * abs(gres.losses[0]),
            f"step-0 loss gap {gaps[0]} to gather exceeds f32 rounding")
    for name, err in errs.items():
        require(err <= decode_tol,
                f"{name} decode differs from gather by {err} > {decode_tol}")

    # -- 4: eval --------------------------------------------------------
    ev = rt.evaluate("val")
    say("4/7 eval", accuracy=ev["accuracy"], loss=ev["loss"], n=ev["n"])
    require(ev["n"] == len(rt.splits["val"]) and np.isfinite(ev["loss"]),
            f"eval covered {ev['n']} nodes, loss {ev['loss']}")

    # -- 5: serve, bitwise against the direct forward -------------------
    engine = rt.serve()
    apply = jax.jit(GNNModel(rt.cfg, interpret=rt.interpret).apply)
    pool = rt.splits["test"]
    for r, n in enumerate(size.serve_sizes):
        ids = pool[7 * r: 7 * r + n]          # overlapping: later requests hit
        h_engine = engine.embed(ids)
        fb = jax.device_put(engine.frontier_for(ids))
        h_direct = np.asarray(apply(rt.params, fb))[:n]
        require(h_engine.shape == (n, rt.cfg.hidden), f"{h_engine.shape}")
        np.testing.assert_array_equal(h_engine, h_direct)
    st = engine.stats()
    say("5/7 serve", requests=list(size.serve_sizes), bitwise=True,
        hits=st.get("hits"), misses=st.get("misses"),
        rows_decoded=st["rows_decoded"], rows_total=st["rows_total"],
        compiles=st["compile_count"])

    # -- 6: host-resident codes, bitwise against device codes -----------
    hrt = GraphRuntime.from_spec(spec.with_updates(codes_placement="host"),
                                 graph=graph)
    hres = hrt.train(2)
    code_bytes = hrt.data_iter.stats()["transferred_code_bytes_per_batch"]
    hrt.close()
    say("6/7 host-codes", losses=hres.losses, device_losses=res.losses[:2],
        code_bytes_per_batch=code_bytes)
    require(hres.losses == res.losses[:2], "host codes diverged from device")
    rt.close()

    # -- 7: memory ------------------------------------------------------
    mem = memory(d0)
    say("7/7 memory", **mem)
    if not tiny:
        require(isinstance(mem["peak_bytes_in_use"], int), f"{mem}")


def four_chips(size: Size, seed: int, tiny: bool) -> None:
    """1 shard vs 4-shard ``sharded:pallas`` vs 4-shard ``owner:pallas``:
    bit-identical step-0 losses, with state and frontier rows on all four
    devices."""
    import jax
    from repro.graph.runtime import GraphRuntime

    spec = build_spec(size, seed)
    graph = spec.graph.build()
    step0 = {}
    for label, n_shards, impl in (("1shard", 1, "pallas"),
                                  ("sharded", 4, "sharded:pallas"),
                                  ("owner", 4, "owner:pallas")):
        rt = GraphRuntime.from_spec(
            spec.with_updates(n_shards=n_shards, lookup_impl=impl),
            graph=graph)
        if not tiny:
            require(rt.interpret is False, "runtime resolved interpret mode")
        fb = peek_batch(rt)["frontier"]
        if impl.startswith("owner"):
            require(fb.plan is not None, "owner plan missing (caps overflowed?)")
        shards = [(s.device.id, s.index[0].start, s.index[0].stop)
                  for s in fb.unique.addressable_shards]
        res = rt.train(3)
        state_devices = sorted(
            d.id for d in jax.tree.leaves(rt.state)[0].sharding.device_set)
        rt.close()
        step0[label] = res.losses[0]
        say(f"4chip {label}", lookup_impl=impl, n_shards=n_shards,
            losses=res.losses, step_s=res.step_times,
            unique_rows=fb.unique.shape[0], unique_shards=shards,
            state_devices=state_devices,
            device_bytes=[memory(d) for d in jax.devices()[:4]])
    same = len(set(step0.values())) == 1
    say("4chip step0", bit_identical=same, **step0)
    require(same, f"step-0 losses differ across shardings: {step0}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal: small graph and widths, interpret "
                         "mode, no platform / tpu_custom_call checks")
    args = ap.parse_args(argv)

    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    d0 = check_platform(args.tiny, args.chips)
    size = TINY if args.tiny else FULL
    if args.chips == 4:
        four_chips(size, args.seed, args.tiny)
    else:
        one_chip(size, args.seed, args.tiny)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
