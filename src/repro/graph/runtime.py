"""GraphRuntime: one declarative spec → train / eval / serve (ISSUE 4).

The paper's value proposition is end-to-end — hash-compressed node
embeddings trained *jointly* with the GNN and then served cheaply at
industrial scale (§5.3).  Every entry point used to re-wire the same
pipeline by hand (graph → codes → state → sampler → batch source →
prefetch → train step → loop); this module is the single front door:

    spec = RuntimeSpec(graph=GraphSource(n_nodes=20_000),
                       model=paper_gnn_config("sage", n_nodes=20_000),
                       optimizer=AdamWConfig(lr=1e-2, weight_decay=0.0))
    rt = GraphRuntime.from_spec(spec)
    rt.train(300)
    rt.evaluate("val"); rt.evaluate("test")
    engine = rt.serve()          # GraphInferenceEngine (serving.gnn)

Everything on the spec is a plain value (JSON round-trip via
``to_json``/``from_dict``), so scaling 1-shard → N-shard, switching the
decode backend, or turning the hot-node cache on is literally a spec field
change — the runtime internally selects ``SageBatchSource`` vs
``ShardedSageBatchSource``, the mesh + frontier placement, prefetch depth,
and the ``lookup_impl`` decode backend from the spec.  Checkpoints written
by ``train`` carry the spec alongside the params, so
``GraphRuntime.resume(ckpt_dir)`` rebuilds the exact pipeline with no other
inputs.

Determinism contract: a runtime built twice from the same spec produces
bit-identical training (graph, codes, init, and the ``(seed, shard, step)``
batch stream are all pure functions of spec fields) — asserted against the
hand-wired pre-runtime path in ``tests/test_runtime.py``.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from repro.configs.base import EmbeddingSpec, GNNConfig
from repro.elastic.manager import ElasticSpec
from repro.graph.engine import (FullGraphBatch, GNNModel, PrefetchIterator,
                                SageBatchSource, ShardedSageBatchSource)
from repro.graph.sampler import NeighborSampler
from repro.optim.adamw import AdamWConfig
from repro.serving.batcher import BatchingSpec

FULLGRAPH_MODELS = ("gcn", "sgc", "gin")


# ---------------------------------------------------------------------------
# spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GraphSource:
    """Declarative graph descriptor (the generators are deterministic in
    their seed, so the descriptor IS the dataset).  ``kind="external"``
    marks a graph handed to ``from_spec(graph=...)`` directly — such specs
    still serialize, but ``resume`` needs the same graph passed again."""

    kind: str = "powerlaw"        # powerlaw | sbm | external
    seed: int = 0
    n_nodes: int = 10_000
    n_classes: int = 16
    avg_degree: int = 10          # powerlaw only
    homophily: float = 0.85       # powerlaw only
    p_in: float = 0.02            # sbm only
    p_out: float = 0.002          # sbm only

    def build(self) -> Tuple[Any, np.ndarray]:
        from repro.graph.generate import powerlaw_graph, sbm_graph
        if self.kind == "powerlaw":
            return powerlaw_graph(self.seed, self.n_nodes,
                                  avg_degree=self.avg_degree,
                                  n_classes=self.n_classes,
                                  homophily=self.homophily)
        if self.kind == "sbm":
            return sbm_graph(self.seed, self.n_nodes, self.n_classes,
                             p_in=self.p_in, p_out=self.p_out)
        if self.kind == "external":
            raise ValueError(
                "GraphSource(kind='external') has no generator — pass the "
                "graph to GraphRuntime.from_spec(spec, graph=(adj, labels))")
        raise ValueError(f"unknown graph kind {self.kind!r}")


@dataclasses.dataclass(frozen=True)
class RuntimeSpec:
    """Everything needed to build the training/eval/serving pipeline.

    The three nested configs (``graph`` / ``model`` / ``optimizer``) plus the
    pipeline knobs below are all plain values; ``to_json`` / ``from_dict``
    round-trip the whole spec, and ``train`` stores it in every checkpoint
    manifest (``GraphRuntime.resume``).

    Scaling knobs (each a pure field change — no new code):
      ``n_shards``            1 → plain ``SageBatchSource``; N → stacked
                              ``ShardedSageBatchSource`` + data-axis mesh +
                              per-shard frontier placement.
      ``model.embedding.lookup_impl``   decode backend (gather / onehot /
                              pallas / sharded[:base] / owner[:base] /
                              auto).  ``owner[:base]`` turns on the
                              owner-computes cross-shard dedup decode: the
                              sharded batch source plans the exchange
                              host-side and hub rows decode once on their
                              owning shard; ``auto`` picks it when the
                              measured duplication beats the threshold.
      ``model.embedding.cache_capacity``/``cache_staleness``  hot-node
                              decode cache in the train state.
      ``model.embedding.cache_plan_misses``  plan-ahead miss partition for
                              cached training: the prefetch thread permutes
                              the next batch's frontier miss-first against a
                              host cache shadow, so the jitted step decodes
                              only (predicted) misses — the training twin of
                              serving's miss-only decode (single-shard).
      ``model.embedding.param_dtype``/``quantize``  decode precision: bf16
                              codebook storage and/or fused absmax-int8
                              (``core.backend.MixedPrecisionPolicy``).
      ``model.embedding.codes_placement``  "host" keeps the packed codes
                              buffer in host RAM: the producer gathers each
                              frontier's code rows into the batch (device
                              code memory is O(frontier), not O(nodes));
                              bitwise-identical to "device".
      ``owner_cap``/``owner_unique_cap``  static owner-exchange capacities
                              (None = sized from ``frontier_cap``, see
                              ``graph.sampler.default_owner_caps``).
      ``prefetch_depth``      0 = synchronous sampling, >0 = async
                              double-buffered host→device pipeline.
    """

    graph: GraphSource
    model: GNNConfig
    optimizer: AdamWConfig = dataclasses.field(
        default_factory=lambda: AdamWConfig(lr=1e-2, weight_decay=0.0))
    # -- data pipeline --
    batch_size: int = 256          # GLOBAL batch (split across shards)
    data_seed: int = 0
    max_deg: int = 64
    pad_to: int = 256
    frontier_cap: Optional[int] = None
    dedup: bool = True
    prefetch_depth: int = 2
    n_shards: int = 1
    owner_cap: Optional[int] = None         # owner-exchange request slots
    owner_unique_cap: Optional[int] = None  # owner-exchange decode rows
    # -- init / splits --
    init_seed: int = 0
    split_seed: int = 0
    split_frac: Tuple[float, float, float] = (0.7, 0.1, 0.2)
    # -- loop --
    total_steps: int = 300
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 100
    log_every: int = 25
    # -- eval / serve --
    eval_batch: int = 512
    eval_seed: int = 17
    serve_batch: int = 256
    # continuous-batching serving tier (serving.batcher); None = bare
    # engine, a BatchingSpec makes rt.serve() return a ServingBatcher
    batching: Optional[BatchingSpec] = None
    # elastic training knobs (repro.elastic); None = defaults when an
    # ElasticManager drives the run, irrelevant otherwise
    elastic: Optional[ElasticSpec] = None
    # pallas interpret mode; None resolves to "not on a TPU runtime",
    # and True on a TPU runtime is an error
    interpret: Optional[bool] = None

    # -- ergonomics ------------------------------------------------------
    def with_updates(self, **kw) -> "RuntimeSpec":
        """Replace fields across the nesting in one call: RuntimeSpec fields
        first, then ``EmbeddingSpec`` fields (``lookup_impl``,
        ``cache_capacity``, ...), then ``GNNConfig`` fields (``fanouts``,
        ``hidden``, ...).  ``spec.with_updates(n_shards=4)`` or
        ``spec.with_updates(lookup_impl="pallas", cache_capacity=4096)``."""
        spec_f = {f.name for f in dataclasses.fields(RuntimeSpec)}
        emb_f = {f.name for f in dataclasses.fields(EmbeddingSpec)}
        model_f = {f.name for f in dataclasses.fields(GNNConfig)}
        spec_kw, emb_kw, model_kw = {}, {}, {}
        for k, v in kw.items():
            if k in spec_f:
                spec_kw[k] = v
            elif k in emb_f:
                emb_kw[k] = v
            elif k in model_f:
                model_kw[k] = v
            else:
                raise TypeError(f"with_updates: unknown field {k!r}")
        model = spec_kw.pop("model", self.model)
        if emb_kw:
            model = dataclasses.replace(
                model, embedding=dataclasses.replace(model.embedding, **emb_kw))
        if model_kw:
            model = dataclasses.replace(model, **model_kw)
        return dataclasses.replace(self, model=model, **spec_kw)

    # -- serialization ---------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict())

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "RuntimeSpec":
        d = dict(d)
        graph = GraphSource(**d.pop("graph"))
        md = dict(d.pop("model"))
        md["embedding"] = EmbeddingSpec(**md["embedding"])
        md["fanouts"] = tuple(md["fanouts"])
        model = GNNConfig(**md)
        opt = AdamWConfig(**d.pop("optimizer"))
        d["split_frac"] = tuple(d["split_frac"])
        if d.get("batching") is not None:
            d["batching"] = BatchingSpec(**d["batching"])
        if d.get("elastic") is not None:
            d["elastic"] = ElasticSpec(**d["elastic"])
        return cls(graph=graph, model=model, optimizer=opt, **d)

    @classmethod
    def from_json(cls, s: str) -> "RuntimeSpec":
        return cls.from_dict(json.loads(s))


# ---------------------------------------------------------------------------
# batch source for the full-graph model family
# ---------------------------------------------------------------------------

class FullGraphSource:
    """Trivial batch source for GCN / SGC / GIN (the paper trains them
    without minibatches, §C.1): every step is the same full-graph handle
    plus the training-node ids/labels.  The batch is device-resident once,
    so the per-step H2D cost is zero."""

    def __init__(self, adj_norm, nodes: np.ndarray, labels: np.ndarray):
        import jax.numpy as jnp
        ids = jnp.asarray(np.asarray(nodes), jnp.int32)
        self._batch = {"full": FullGraphBatch(adj_norm),
                       "ids": ids,
                       "labels": jnp.asarray(np.asarray(labels)[nodes],
                                             jnp.int32)}
        self.step = 0

    def next_batch(self) -> Dict[str, Any]:
        self.step += 1
        return self._batch

    def state_dict(self) -> Dict[str, int]:
        return {"step": self.step}

    def load_state_dict(self, state: Dict[str, int]) -> None:
        self.step = int(state["step"])


# ---------------------------------------------------------------------------
# runtime
# ---------------------------------------------------------------------------

class GraphRuntime:
    """Facade over the streaming graph engine: build once from a spec, then
    ``train`` / ``evaluate`` / ``embed`` / ``serve``.

    Construction (``from_spec``) wires graph → codes → state → sampler →
    batch source → placement → train step exactly the way the pre-runtime
    entry points did by hand, so spec-built training is bit-identical to the
    hand-wired path (tests/test_runtime.py).  Benchmarks that need to drive
    steps manually use the exposed attributes (``state``, ``data_iter``,
    ``jitted_step``, ``place``) instead of re-wiring.
    """

    def __init__(self, spec: RuntimeSpec, *, adj, labels):
        self.spec = spec
        cfg = spec.model
        if spec.graph.kind != "external" and cfg.n_nodes != spec.graph.n_nodes:
            raise ValueError(
                f"model.n_nodes {cfg.n_nodes} != graph.n_nodes "
                f"{spec.graph.n_nodes}")
        if adj.shape[0] != cfg.n_nodes:
            raise ValueError(
                f"graph has {adj.shape[0]} nodes, model expects {cfg.n_nodes}")
        self.adj = adj
        self.labels = np.asarray(labels)
        self.cfg = cfg
        on_tpu = jax.default_backend() == "tpu"
        if spec.interpret and on_tpu:
            raise ValueError(
                "RuntimeSpec.interpret=True on a TPU runtime would run the "
                "Pallas kernels in the interpreter; leave it None")
        self.interpret = (spec.interpret if spec.interpret is not None
                          else not on_tpu)
        self.fullgraph = cfg.model in FULLGRAPH_MODELS

        # -- codes + state (pure functions of the spec seeds) -------------
        from repro.core import embedding as emb_lib
        from repro.train import init_gnn_train_state, make_gnn_train_step
        key = jax.random.PRNGKey(spec.init_seed)
        ecfg = cfg.embedding_config()
        self.codes = None
        self.codes_on_host = ecfg.codes_on_host
        if self.codes_on_host and self.fullgraph:
            raise ValueError(
                "codes_placement='host' needs the sampled (frontier) model "
                "family — full-graph models decode every node per step, so "
                "there is no O(frontier) working set to stream")
        if ecfg.needs_codes:
            # numpy copy: the train state is donated per step, so a shared
            # device buffer would be deleted out from under a later rebuild
            # (the hashemb family needs no codes at all: position hashes are
            # recomputed from the ids at every lookup).  With
            # codes_placement="host" this numpy array IS the authoritative
            # buffer — params carry no codes_buf at all.
            self.codes = np.asarray(
                emb_lib.make_codes(key, ecfg, aux=adj))
        self.state = init_gnn_train_state(key, cfg, codes=self.codes)
        self.model = GNNModel(cfg, interpret=self.interpret)
        self._code_gather: Optional[Callable[[Any], Any]] = None
        if self.codes_on_host:
            from repro.graph.sampler import attach_codes
            host_codes = self.codes

            def _gather(batch):
                if isinstance(batch, dict) and "frontier" in batch:
                    batch = dict(batch)
                    batch["frontier"] = attach_codes(batch["frontier"],
                                                     host_codes)
                return batch
            self._code_gather = _gather

        # -- splits --------------------------------------------------------
        from repro.graph.generate import train_val_test_split
        tr, va, te = train_val_test_split(spec.split_seed, cfg.n_nodes,
                                          spec.split_frac)
        self.splits = {"train": tr, "val": va, "test": te}

        # -- mesh / placement (n_shards is the whole N-shard switch) -------
        self.mesh = None
        self.place: Callable[[Any], Any] = lambda b: b
        if spec.n_shards > 1:
            from repro.parallel.policy import make_frontier_placement
            from repro.parallel.sharding import data_mesh
            from jax.sharding import NamedSharding, PartitionSpec
            self.mesh = data_mesh(spec.n_shards)
            self.place = make_frontier_placement(self.mesh)
            # the step returns the state replicated over the mesh; placing
            # it so up front keeps the second step from compiling again
            self.state = jax.device_put(
                self.state, NamedSharding(self.mesh, PartitionSpec()))

        # -- sampler + batch source ----------------------------------------
        if self.fullgraph:
            # no neighbour table: full-graph models never sample, and the
            # (n_nodes, max_deg) table is real memory at scale
            self.sampler = None
            adjn = adj.with_self_loops().normalized("sym")
            self.adj_norm = adjn
            self.source = FullGraphSource(adjn, tr, self.labels)
        else:
            self.sampler = NeighborSampler(adj, cfg.fanouts,
                                           max_deg=spec.max_deg,
                                           seed=spec.data_seed)
            self.adj_norm = None
            if spec.n_shards > 1:
                if spec.batch_size % spec.n_shards:
                    raise ValueError(
                        f"batch_size {spec.batch_size} not divisible by "
                        f"n_shards {spec.n_shards}")
                # owner-computes decode: the batch source plans the exchange
                # host-side whenever the backend can exploit it — always for
                # an explicit "owner[:base]" impl, measured-duplication-gated
                # for "auto" (the same threshold resolve_auto applies)
                impl = (cfg.embedding.lookup_impl or "auto").split(":")[0]
                owner_plan = (True if impl == "owner"
                              else ("auto" if impl == "auto" else False))
                self.source = ShardedSageBatchSource(
                    self.sampler, tr, self.labels,
                    spec.batch_size // spec.n_shards,
                    n_shards=spec.n_shards, seed=spec.data_seed,
                    pad_to=spec.pad_to, frontier_cap=spec.frontier_cap,
                    owner_plan=owner_plan, owner_cap=spec.owner_cap,
                    owner_unique_cap=spec.owner_unique_cap)
            else:
                self.source = SageBatchSource(
                    self.sampler, tr, self.labels, spec.batch_size,
                    seed=spec.data_seed, dedup=spec.dedup,
                    pad_to=spec.pad_to, frontier_cap=spec.frontier_cap)
            emb = cfg.embedding
            if emb.cache_plan_misses:
                # plan-ahead miss partition: the producer thread permutes the
                # next frontier miss-first against a host cache shadow, so
                # the train step's decode covers only (predicted) misses
                if emb.cache_capacity <= 0 or not cfg.embedding_config().is_compressed:
                    raise ValueError(
                        "cache_plan_misses needs a hot-node cache on a "
                        "compressed embedding (cache_capacity > 0)")
                if spec.n_shards > 1 or not spec.dedup:
                    raise ValueError(
                        "cache_plan_misses is single-shard dedup only: the "
                        "miss-first permutation breaks stacked per-shard row "
                        "blocks and owner-plan row indexing")
                from repro.graph.engine import MissPlanningSource
                self.source = MissPlanningSource(
                    self.source, emb.cache_capacity, emb.cache_staleness,
                    pad_to=spec.pad_to)

        # -- iterator (prefetch is a knob, not a code path) ----------------
        if spec.prefetch_depth > 0 and not self.fullgraph:
            device = self.place if self.mesh is not None else None
            # codes_placement="host": the producer thread gathers batch
            # k+1's code rows (and completes their H2D copy) while the
            # device computes batch k
            self.data_iter = PrefetchIterator(self.source,
                                              depth=spec.prefetch_depth,
                                              device=device,
                                              code_gather=self._code_gather)
            self._to_device: Callable[[Any], Any] = lambda b: b
        else:
            self.data_iter = self.source
            place = self.place if self.mesh is not None else (lambda b: b)
            if self._code_gather is not None:
                gather = self._code_gather
                self._to_device = lambda b: place(gather(b))
            else:
                self._to_device = place

        # -- step + checkpointing ------------------------------------------
        self.train_step = make_gnn_train_step(
            cfg, spec.optimizer, interpret=self.interpret, mesh=self.mesh,
            duplication=getattr(self.source, "duplication_measured", None))
        self._jitted_step = None
        self.ckpt = None
        if spec.ckpt_dir:
            from repro.train import CheckpointManager
            self.ckpt = CheckpointManager(spec.ckpt_dir, keep=2)
        self._eval_fns: Dict[Any, Callable] = {}

    # -- construction ----------------------------------------------------
    @classmethod
    def from_spec(cls, spec: RuntimeSpec,
                  graph: Optional[Tuple[Any, np.ndarray]] = None
                  ) -> "GraphRuntime":
        """Build the full pipeline from a spec.  ``graph`` overrides the
        declarative ``spec.graph`` generator with a pre-built
        ``(adj, labels)`` pair (required when ``graph.kind == "external"``,
        an optional rebuild-saver otherwise)."""
        if graph is None:
            adj, labels = spec.graph.build()
        else:
            adj, labels = graph
        return cls(spec, adj=adj, labels=labels)

    @classmethod
    def resume(cls, ckpt_dir: str,
               graph: Optional[Tuple[Any, np.ndarray]] = None
               ) -> "GraphRuntime":
        """Rebuild a runtime from the spec stored in ``ckpt_dir``'s latest
        checkpoint manifest AND restore its params/opt/data state, so
        ``evaluate`` / ``embed`` / ``serve`` right after resume see the
        trained model (a later ``train`` call re-restores idempotently and
        continues the exact step sequence)."""
        from repro.train import CheckpointManager
        extra = CheckpointManager(ckpt_dir).read_extra()
        if extra is None or "spec" not in extra:
            raise FileNotFoundError(
                f"no checkpoint with a runtime spec under {ckpt_dir!r}")
        spec = RuntimeSpec.from_dict(extra["spec"])
        spec = dataclasses.replace(spec, ckpt_dir=ckpt_dir)
        rt = cls.from_spec(spec, graph=graph)
        restored = rt.ckpt.restore_latest(rt.state)
        if restored is not None:
            _step, state, rextra = restored
            rt.state = state
            if "data" in rextra and hasattr(rt.data_iter, "load_state_dict"):
                rt.data_iter.load_state_dict(rextra["data"])
            # miss-planning runs: re-anchor the host cache shadow to the
            # restored device cache (exact even for state dicts that predate
            # the shadow snapshot key)
            src = getattr(rt.data_iter, "source", rt.data_iter)
            if hasattr(src, "sync_shadow") and "cache" in rt.state:
                src.sync_shadow(rt.state["cache"])
        return rt

    # -- training --------------------------------------------------------
    @property
    def params(self):
        return self.state["params"]

    @property
    def jitted_step(self):
        """The donated-state jitted train step (for benchmarks that time
        steps manually; ``train`` uses its own via ``run_training``)."""
        if self._jitted_step is None:
            self._jitted_step = jax.jit(self.train_step, donate_argnums=(0,))
        return self._jitted_step

    def train(self, steps: Optional[int] = None,
              on_metrics: Optional[Callable[[int, Dict], None]] = None,
              fence: Optional[Callable[[int], None]] = None):
        """Run the generic fault-tolerant loop for ``steps`` (default
        ``spec.total_steps``) and absorb the resulting state.

        With ``spec.ckpt_dir`` set, ``steps`` is the absolute target step
        count: the loop auto-resumes from the newest checkpoint (params,
        optimizer, data-pipeline state AND the spec ride in every manifest)
        and trains the remaining gap.  Without a checkpoint dir it simply
        runs ``steps`` more steps.  Returns the ``LoopResult``.

        Every checkpoint manifest is stamped with the run's shard topology
        and auto-resume validates it (``train.TopologyMismatch`` on a
        mismatch — rescale via ``GraphRuntime.rescale`` instead).

        ``fence``: step-fence callback (``run_training``), the hook
        ``repro.elastic.ElasticManager`` drives liveness through; it may
        raise ``FenceInterrupt`` to stop at a step boundary."""
        from repro.train import LoopConfig, run_training
        spec = self.spec
        total = int(steps if steps is not None else spec.total_steps)
        res = run_training(
            self.jitted_step, self.state, self.data_iter,
            LoopConfig(total_steps=total, ckpt_every=spec.ckpt_every,
                       log_every=spec.log_every),
            ckpt=self.ckpt, to_device=self._to_device, on_metrics=on_metrics,
            extra_base={"spec": self.spec.to_dict()}, prejitted=True,
            fence=fence,
            topology={"n_shards": spec.n_shards,
                      "batch_size": spec.batch_size})
        self.state = res.state
        return res

    # -- elastic rescale -------------------------------------------------
    def rescale(self, n_shards: int, ckpt_dir: Optional[str] = None
                ) -> "GraphRuntime":
        """Exact in-process rescale: a new runtime at ``n_shards`` that
        continues this run's state and batch stream bit-identically to a
        native ``n_shards``-shard run (``repro.elastic.rescale`` has the
        argument; requires the global ``batch_size`` to divide evenly).
        The old runtime stays usable; close it when done.  ``ckpt_dir``
        names a *fresh* checkpoint directory for the rescaled run — the
        old one is stamped with the old topology and stays behind."""
        from repro.elastic.rescale import rescale_runtime
        return rescale_runtime(self, n_shards, ckpt_dir=ckpt_dir)

    @classmethod
    def rescale_checkpoint(cls, ckpt_dir: str, n_shards: int,
                           graph: Optional[Tuple[Any, np.ndarray]] = None,
                           new_ckpt_dir: Optional[str] = None
                           ) -> "GraphRuntime":
        """The sanctioned cross-topology resume: rebuild the checkpointed
        run at its *original* shard count (topology check passes by
        construction), then exact-rescale to ``n_shards``.  This is the
        path the ``TopologyMismatch`` error message points at."""
        rt = cls.resume(ckpt_dir, graph=graph)
        try:
            return rt.rescale(n_shards, ckpt_dir=new_ckpt_dir)
        finally:
            rt.close()

    # -- evaluation ------------------------------------------------------
    def _eval_fn(self, kind: str):
        if kind not in self._eval_fns:
            model = self.model
            def fn(params, batch):
                h = model.apply(params, batch)
                return model.logits(params, h)
            self._eval_fns[kind] = jax.jit(fn)
        return self._eval_fns[kind]

    def evaluate(self, split: str = "val",
                 batch_size: Optional[int] = None) -> Dict[str, float]:
        """Deterministic accuracy/loss over a named split ("train" / "val" /
        "test").  GraphSAGE evaluates in fixed-size frontier minibatches
        (neighbour draws seeded by ``(eval_seed, batch index)``, so repeat
        calls are identical); full-graph models evaluate in one pass.  The
        final short batch is padded and the padding masked out, so every
        split node counts exactly once."""
        from repro.models import gnn as gnn_lib
        nodes = self.splits[split]
        params = self.state["params"]
        if self.fullgraph:
            logits = np.asarray(
                self._eval_fn("full")(params, FullGraphBatch(self.adj_norm)))
            logits = logits[nodes]
            labels = self.labels[nodes]
            loss = float(gnn_lib.node_loss(jax.numpy.asarray(logits),
                                           jax.numpy.asarray(labels)))
            acc = float((logits.argmax(-1) == labels).mean())
            return {"accuracy": acc, "loss": loss, "n": int(len(nodes))}

        bs = int(batch_size or self.spec.eval_batch)
        eval_fn = self._eval_fn("sage")
        correct, loss_sum, seen = 0, 0.0, 0
        for bi, s in enumerate(range(0, len(nodes), bs)):
            batch = np.asarray(nodes[s:s + bs])
            n_real = batch.shape[0]
            if n_real < bs:                      # pad (masked out below)
                batch = np.concatenate(
                    [batch, np.full(bs - n_real, batch[0], batch.dtype)])
            rng = np.random.default_rng(
                (self.spec.eval_seed * 1_000_003 + 12_582_917) + bi)
            fb = self.sampler.sample_frontier(batch.astype(np.int32),
                                              pad_to=self.spec.pad_to,
                                              rng=rng)
            if self.codes_on_host:
                from repro.graph.sampler import attach_codes
                fb = attach_codes(fb, self.codes)
            logits = np.asarray(eval_fn(params, jax.device_put(fb)))[:n_real]
            labels = self.labels[batch[:n_real]]
            correct += int((logits.argmax(-1) == labels).sum())
            lj = jax.numpy.asarray(logits)
            loss_sum += float(gnn_lib.node_loss(
                lj, jax.numpy.asarray(labels))) * n_real
            seen += n_real
        return {"accuracy": correct / max(seen, 1),
                "loss": loss_sum / max(seen, 1), "n": seen}

    # -- inference -------------------------------------------------------
    def embed(self, node_ids) -> np.ndarray:
        """Final hidden representations (B, H) for ``node_ids`` through the
        current params (direct forward — for a cached, fixed-shape serving
        path use ``serve()``)."""
        ids = np.asarray(node_ids, np.int32)
        if self.fullgraph:
            h = self.model.apply(self.state["params"],
                                 FullGraphBatch(self.adj_norm))
            return np.asarray(h)[ids]
        rng = np.random.default_rng(self.spec.eval_seed)
        fb = self.sampler.sample_frontier(ids, pad_to=self.spec.pad_to,
                                          rng=rng)
        if self.codes_on_host:
            from repro.graph.sampler import attach_codes
            fb = attach_codes(fb, self.codes)
        return np.asarray(
            self.model.apply(self.state["params"], jax.device_put(fb)))

    def serve(self, *, batching=None, **overrides):
        """Freeze the current params into a ``GraphInferenceEngine`` (the
        GNN twin of ``serving.DecodeEngine``): batched frontier sampling,
        miss-only hot-node cached decode, fixed-shape jit.  Keyword
        overrides are forwarded to the engine constructor.

        ``batching`` selects the continuous-batching tier
        (``serving.ServingBatcher``, see ``docs/serving.md``): ``None``
        defers to ``spec.batching``; a ``BatchingSpec`` (or ``True`` for
        defaults) wraps the engine in a batcher whose microbatches get
        cross-request frontier dedup; ``False`` forces the bare engine even
        when the spec asks for batching.  The batcher owns the engine —
        ``close()`` it (or use it as a context manager) when done."""
        if self.fullgraph:
            raise NotImplementedError(
                "serving is minibatched GraphSAGE only; full-graph models "
                "evaluate via runtime.evaluate()")
        from repro.serving.gnn import GraphInferenceEngine
        if batching is None:
            batching = self.spec.batching
        if batching is True:
            batching = BatchingSpec()
        kw = dict(serve_batch=self.spec.serve_batch, pad_to=self.spec.pad_to,
                  interpret=self.interpret)
        if self.codes_on_host:
            # the engine gathers each (possibly permuted) serving frontier's
            # code rows from this buffer — device stays O(frontier)
            kw.setdefault("host_codes", self.codes)
        if batching:
            # engine request-count buckets must admit the batcher's flushes
            kw.setdefault("max_coalesce", batching.max_batch)
        kw.update(overrides)
        engine = GraphInferenceEngine(self.cfg, self.state["params"],
                                      self.sampler, **kw)
        if not batching:
            return engine
        from repro.serving.batcher import ServingBatcher
        return ServingBatcher(engine, batching)

    def close(self) -> None:
        if hasattr(self.data_iter, "close"):
            self.data_iter.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
