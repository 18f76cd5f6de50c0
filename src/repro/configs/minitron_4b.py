"""minitron-4b [dense] — 32L d_model=3072 24H (GQA kv=8) d_ff=9216
vocab=256000; Nemotron-4 15B pruned and distilled: ungated relu² MLP,
LayerNorm1P, rotary on half of each head, no embedding scale, untied
head. [arXiv:2407.14679; https://huggingface.co/nvidia/Minitron-4B-Base]"""
from repro.models.common import ArchConfig

CONFIG = ArchConfig(
    name="minitron-4b",
    family="dense",
    n_layers=32,
    d_model=3072,
    n_heads=24,
    n_kv=8,
    d_ff=9216,
    vocab=256000,
    cycle=("attn",),
    head_dim=128,
    rope_theta=10_000.0,
    rotary_dim=64,           # partial_rotary_factor 0.5
    norm_type="layernorm1p",
    act="relu2",
    mlp_gated=False,
    embed_scale=False,
    tie_embeddings=False,
)
