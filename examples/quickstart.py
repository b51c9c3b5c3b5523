"""Quickstart: the paper's pipeline end to end through ONE declarative spec.

1. Describe everything — graph, GNN + compressed embedding, optimizer,
   pipeline knobs — in a ``RuntimeSpec`` (plain values, JSON round-trip).
2. ``GraphRuntime.from_spec`` builds the whole thing: the graph, Algorithm-1
   codes (training-free), the decoder + GNN state, the dedup-decode sampler
   pipeline.
3. Train the decoder jointly with the task, evaluate the held-out splits.
4. Serve batched requests through the ``GraphInferenceEngine`` (miss-only
   hot-node cached decode — only cache misses pay the decoder).
5. Compare the memory footprint with the uncompressed table.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.configs.paper_gnn import paper_gnn_config
from repro.compile_cache import enable_compile_cache
from repro.core.memory import memory_breakdown, MiB
from repro.graph.runtime import GraphRuntime, GraphSource, RuntimeSpec
from repro.nn.module import param_bytes
from repro.optim import AdamWConfig

N_NODES = 20_000


def main():
    # -- 1. one spec = the whole pipeline ---------------------------------
    spec = RuntimeSpec(
        graph=GraphSource(kind="powerlaw", seed=0, n_nodes=N_NODES,
                          n_classes=16, avg_degree=8),
        model=paper_gnn_config("sage", n_nodes=N_NODES, n_classes=16,
                               kind="hash_full", fanout=10),
        optimizer=AdamWConfig(lr=1e-2, weight_decay=0.0),
        batch_size=256,
        total_steps=100,
        log_every=25,
    ).with_updates(d_c=128, d_m=128)     # reduced decoder so CPU stays snappy
    print(f"spec round-trips to {len(spec.to_json())} bytes of JSON")

    # -- 2. build: graph + Algorithm-1 codes + state ----------------------
    rt = GraphRuntime.from_spec(spec)
    cfg = spec.model.embedding
    print(f"graph: {N_NODES} nodes, {rt.adj.nnz} edges")
    print(f"codes: {rt.codes.shape} uint32 (c={cfg.c}, m={cfg.m} per node)")

    # -- 3. decoder trains with the downstream task -----------------------
    rt.train(on_metrics=lambda s, m: print(f"step {s:3d}  loss {m['loss']:.4f}"))
    va, te = rt.evaluate("val"), rt.evaluate("test")
    print(f"val acc {va['accuracy']:.4f} / test acc {te['accuracy']:.4f} "
          f"(chance {1/16:.4f})")

    # -- 4. serve: hot nodes decode once, repeats hit the cache -----------
    engine = rt.serve(serve_batch=128)
    rng = np.random.default_rng(0)
    for i in range(3):
        res = engine.serve(rng.integers(0, N_NODES, 128))
        print(f"request {i}: decoded {res.rows_decoded}/{res.rows_total} "
              f"frontier rows, predictions {res.predictions[:6]}...")
    print(f"serving stats: {engine.stats()}")
    rt.close()

    # -- 5. memory: the decoder is a FIXED cost (paper Table 4) -----------
    b = memory_breakdown(N_NODES, 64, cfg.c, cfg.m, cfg.d_c, cfg.d_m, 3)
    print(f"\nraw table    : {b.raw_table_bytes / MiB:8.2f} MiB")
    print(f"codes        : {b.binary_code_bytes / MiB:8.2f} MiB")
    print(f"decoder      : {b.trainable_decoder_bytes / MiB:8.2f} MiB")
    print(f"ratio        : {b.ratio_total:8.2f}x")
    emb_params = rt.params["embed"]
    print(f"trainable params do not grow with nodes: "
          f"{param_bytes(emb_params, trainable_only=True) / MiB:.2f} MiB")
    for n in (100_000, 1_871_031, 1_000_000_000):
        bb = memory_breakdown(n, 64, cfg.c, cfg.m, cfg.d_c, cfg.d_m, 3)
        print(f"  at n={n:>13,}: raw {bb.raw_table_bytes/MiB:10.1f} MiB -> "
              f"compressed {bb.compressed_total/MiB:8.1f} MiB  "
              f"({bb.ratio_total:6.1f}x)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
