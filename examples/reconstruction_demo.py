"""Figure-1-style demo: watch random coding fall behind hashing as the
number of compressed entities grows (the paper's core observation).

Run:  PYTHONPATH=src python examples/reconstruction_demo.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import kmeans, nmi
from benchmarks.fig1_reconstruction import _train_decoder_on_reconstruction
from repro.compile_cache import enable_compile_cache
from repro.core import lsh
from repro.core.embedding import decode_all
from repro.graph.generate import clustered_embeddings


def main():
    key = jax.random.PRNGKey(0)
    print(f"{'entities':>9} {'raw':>7} {'random':>7} {'hashing':>8}")
    for n in (1000, 4000, 8000):
        emb, labels = clustered_embeddings(0, n, 64, 8, noise=0.35)
        embj = jnp.asarray(emb)
        raw = nmi(kmeans(emb[:1000], 8), labels[:1000])
        row = {"raw": raw}
        for scheme in ("random", "hashing"):
            codes = (lsh.encode_random(key, n, 16, 16) if scheme == "random"
                     else lsh.encode_lsh(key, embj, 16, 16))
            params, cfg, _ = _train_decoder_on_reconstruction(key, embj, codes,
                                                              n_steps=200)
            rec = np.asarray(decode_all(params, cfg))
            row[scheme] = nmi(kmeans(rec[:1000], 8), labels[:1000])
        print(f"{n:>9} {row['raw']:7.3f} {row['random']:7.3f} "
              f"{row['hashing']:8.3f}")
    print("\nexpected: the hashing column stays near raw; random decays with n.")


if __name__ == "__main__":
    enable_compile_cache()
    main()
