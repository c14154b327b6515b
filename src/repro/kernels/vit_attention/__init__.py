from repro.kernels.vit_attention.ops import vit_attention  # noqa: F401
