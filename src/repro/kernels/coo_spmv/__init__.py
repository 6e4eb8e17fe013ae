from repro.kernels.coo_spmv.ops import (CooTiles, build_tiles,  # noqa: F401
                                        coo_spmv, kernel_applies)
