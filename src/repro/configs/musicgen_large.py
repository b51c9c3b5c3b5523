"""musicgen-large [audio] — decoder-only over EnCodec tokens.
48L d_model=2048 32H (MHA) d_ff=8192 vocab=2048 (per codebook, 4 codebooks,
delay-pattern interleaving).  [arXiv:2306.05284; hf]

Frontend stub: inputs are the 4 parallel EnCodec token streams (B, S, 4);
the 4 codebook embeddings are summed (MusicGen's own input path); the head
predicts 4x2048 logits per step.  Sinusoidal positions (no RoPE), LayerNorm
+ GELU per the original transformer recipe.

Paper-technique note: vocab 2,048/codebook is tiny — the
hash-compressed table is LARGER than dense at paper hyper-params (ratio<1),
so `dense` is the default; compressed kinds remain selectable for ablation.
"""

from repro.configs.base import EmbeddingSpec, LMConfig, register


@register("musicgen-large")
def config() -> LMConfig:
    return LMConfig(
        name="musicgen-large",
        family="audio",
        n_layers=48,
        d_model=2048,
        vocab_size=2048,
        n_heads=32,
        n_kv_heads=32,
        d_ff=8192,
        rope_variant="none",
        act="gelu",
        norm="layernorm",
        input_mode="audio_tokens",
        n_codebooks=4,
        embedding=EmbeddingSpec(kind="dense"),
        notes="hash embedding inapplicable in practice: n=2048/codebook gives ratio<1",
    )
