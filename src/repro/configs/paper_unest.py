"""paper-unest — the language-model-shaped backbone of ``segment_unest``
(``core/pipelines.py:_segment_fn``): a dense 12x512 transformer stack with
rotary positions, run there as its ``.reduced()`` preset over 3D patches.
It is not UNesT's shape: the published UNesT model (Yu et al. 2023,
arXiv:2209.14378; hierarchical block attention and a conv decoder) runs as
the ``unest_wholebrain`` stage (``core/unest.py``), which uses nothing
here."""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="paper-unest",
    family="dense",
    n_layers=12,
    d_model=512,
    n_heads=8,
    n_kv_heads=8,
    d_ff=2048,
    vocab_size=4096,          # voxel-patch codebook
    d_head=64,
    mlp="gelu",
    source="arXiv:2209.14378 (UNesT); paper §2.1",
)
