from repro.kernels.ops import (  # noqa: F401
    accumulate,
    fuse_quantized,
    fuse_updates,
    interpret_mode,
    quantize_update,
)
