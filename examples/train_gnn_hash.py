"""End-to-end driver (paper §5.2/§5.3 scenario) through the GraphRuntime:
GraphSAGE + hash-compressed node embeddings trained jointly, evaluated on
the held-out splits, all from ONE declarative ``RuntimeSpec``.

The runtime owns the whole pipeline (graph → codes → state → sampler →
batch source → prefetch → train step → fault-tolerant loop), so this file
contains zero wiring: scaling to N shards, switching the decode backend or
enabling the hot-node cache are spec field changes (`--shards`,
``spec.with_updates(lookup_impl=..., cache_capacity=...)``).  Checkpoints
carry the spec, so killing this script mid-run and re-running continues
from the last checkpoint.

Run:  PYTHONPATH=src python examples/train_gnn_hash.py [--steps 300]
      [--kind hash_full|random_full|dense] [--nodes 20000] [--no-prefetch]
      [--shards N]
"""

import argparse
import time

from repro.configs.paper_gnn import paper_gnn_config
from repro.compile_cache import enable_compile_cache
from repro.graph.runtime import GraphRuntime, GraphSource, RuntimeSpec
from repro.optim import AdamWConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--nodes", type=int, default=20000)
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--kind", default="hash_full")
    ap.add_argument("--ckpt-dir", default="/tmp/hashemb_gnn_run")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable the async host->device pipeline")
    ap.add_argument("--shards", type=int, default=1,
                    help="data-parallel shards (needs >= N jax devices)")
    args = ap.parse_args()

    spec = RuntimeSpec(
        graph=GraphSource(kind="powerlaw", seed=0, n_nodes=args.nodes,
                          n_classes=args.classes, avg_degree=10,
                          homophily=0.85),
        model=paper_gnn_config("sage", n_nodes=args.nodes,
                               n_classes=args.classes, kind=args.kind,
                               fanout=10),
        optimizer=AdamWConfig(lr=1e-2, weight_decay=0.0),
        batch_size=256,
        prefetch_depth=0 if args.no_prefetch else 2,
        n_shards=args.shards,
        total_steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=100,
        log_every=25,
    )

    t0 = time.time()
    rt = GraphRuntime.from_spec(spec)
    print(f"[build] {args.nodes} nodes / {rt.adj.nnz} edges, "
          f"codes {None if rt.codes is None else tuple(rt.codes.shape)}, "
          f"{args.shards} shard(s) in {time.time()-t0:.1f}s")

    def on_metrics(step, m):
        print(f"[step {step:4d}] loss={m['loss']:.4f} "
              f"({m['step_time']*1e3:.0f} ms/step, ewma {m['ewma']*1e3:.0f} ms)")

    t0 = time.time()
    res = rt.train(on_metrics=on_metrics)
    if res.resumed_from is not None:
        print(f"[resume] continued from step {res.resumed_from}")
    print(f"[train] {len(res.losses)} steps in {time.time()-t0:.1f}s "
          f"({res.stragglers} stragglers)")

    # held-out splits: the runtime evaluates val AND test (paper protocol:
    # model selection on val, report test)
    va = rt.evaluate("val")
    te = rt.evaluate("test")
    print(f"[eval] val  acc = {va['accuracy']:.4f}  (loss {va['loss']:.4f}, "
          f"n={va['n']})")
    print(f"[eval] test acc = {te['accuracy']:.4f}  (loss {te['loss']:.4f}, "
          f"n={te['n']}, chance = {1/args.classes:.4f})")
    rt.close()


if __name__ == "__main__":
    enable_compile_cache()
    main()
