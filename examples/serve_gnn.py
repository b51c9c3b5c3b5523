"""Serve batched GNN requests through the runtime's inference engine —
the §5.3 merchant-system shape: train hash-compressed node embeddings
jointly with GraphSAGE, freeze, then answer node-classification requests.

``GraphInferenceEngine`` (the GNN twin of ``serving.DecodeEngine`` behind
the shared ``serving.Engine`` protocol) samples each request's frontier,
partitions it host-side against the hot-node cache, and decodes ONLY the
misses — watch ``rows_decoded`` collapse between the first request and the
repeats.

Run:  PYTHONPATH=src python examples/serve_gnn.py [--nodes 8000]
      [--steps 50] [--requests 8] [--batch 128]
"""

import argparse
import time

import numpy as np

from repro.configs.paper_gnn import paper_gnn_config
from repro.compile_cache import enable_compile_cache
from repro.graph.runtime import GraphRuntime, GraphSource, RuntimeSpec
from repro.optim import AdamWConfig
from repro.serving import Engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=8000)
    ap.add_argument("--classes", type=int, default=8)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=128)
    args = ap.parse_args()

    spec = RuntimeSpec(
        graph=GraphSource(kind="powerlaw", seed=0, n_nodes=args.nodes,
                          n_classes=args.classes, avg_degree=10,
                          homophily=0.9),
        model=paper_gnn_config("sage", n_nodes=args.nodes,
                               n_classes=args.classes, fanout=5),
        optimizer=AdamWConfig(lr=1e-2, weight_decay=0.0),
        batch_size=256,
        total_steps=args.steps,
        log_every=max(args.steps // 4, 1),
    ).with_updates(c=64, m=8, d_c=128, d_m=128)

    rt = GraphRuntime.from_spec(spec)
    print(f"[train] {args.steps} steps ...")
    rt.train()
    print(f"[eval] val acc = {rt.evaluate('val')['accuracy']:.4f}")

    engine = rt.serve(serve_batch=args.batch)
    assert isinstance(engine, Engine)   # shared serving protocol
    print(f"[serve] batch={args.batch}, frontier cap={engine.frontier_cap}, "
          f"cache={engine.cache_capacity} slots")

    rng = np.random.default_rng(0)
    for i in range(args.requests):
        ids = rng.integers(0, args.nodes, args.batch)
        t0 = time.perf_counter()
        res = engine.serve(ids)
        dt = (time.perf_counter() - t0) * 1e3
        print(f"[req {i:2d}] {dt:7.1f} ms  decoded "
              f"{res.rows_decoded:5d}/{res.rows_total} rows  "
              f"top classes {np.bincount(res.predictions).argmax()}")
    stats = engine.stats()
    print(f"[done] hit_rate={stats.get('hit_rate', 0.0):.2f}  "
          f"rows_decoded={stats['rows_decoded']}/{stats['rows_total']} "
          f"({1 - stats['rows_decoded'] / stats['rows_total']:.0%} of decode "
          f"work served from the hot-node cache)")
    rt.close()


if __name__ == "__main__":
    enable_compile_cache()
    main()
