"""HashEmb: hash-based embedding compression (Yeh et al., KDD 2022) as a
first-class feature of a multi-pod JAX training/serving framework.

Subpackages
-----------
core      the paper's contribution: LSH coding, compositional codes, decoder
kernels   Pallas TPU kernels (hash_decode, lsh_encode, flash_attention)
nn        neural-net substrate (attention, MoE, SSD, norms, module system)
models    LM family (dense/MoE/SSM/hybrid) and GNNs (SAGE/GCN/SGC/GIN)
graph     CSR graphs, synthetic generators, neighbor sampling
data      synthetic token pipelines, checkpointable iterators
optim     AdamW, schedules, gradient compression
train     train-step factory, loop, checkpointing, fault tolerance
serving   single-token decode engine
parallel  logical-axis sharding rules, mesh helpers
configs   architecture registry (the 10 assigned archs + paper GNN stack)
"""

__version__ = "1.0.0"
