from repro.kernels.ops import (  # noqa: F401
    SlabAcc,
    accumulate,
    fuse_quantized,
    fuse_updates,
    interpret_mode,
    quantize_update,
    slab_plan,
)
