"""Pallas TPU kernels for the framework's compute hot-spots.

hash_decode     fused one-hot x codebook decode (the paper's hot op on TPU)
lsh_encode      streaming projection + binarise + bit-pack (Algorithm 1)
flash_attention blocked online-softmax attention w/ native GQA (LM backbone)

Each package: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd wrapper,
custom VJP, oracle fallback), ref.py (pure-jnp oracle).  Kernels validate in
interpret mode on CPU; TPU is the deployment target.
"""
