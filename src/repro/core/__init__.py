"""The paper's contribution as composable JAX modules.

Stage 1 (Alg. 1)   :mod:`repro.core.similarity` — sparse similarity graphs.
Stage 2 (Alg. 2-3) :mod:`repro.core.laplacian`, :mod:`repro.core.lanczos` —
                   normalized Laplacian + on-device restarted Lanczos.
Stage 3 (Alg. 4-5) :mod:`repro.core.kmeans` — k-means++ / fused Lloyd.
End-to-end         :mod:`repro.core.pipeline` (+ ``distributed_pipeline``).

NOTE: ``repro.core.kmeans`` (module) contains ``kmeans`` (function) — we do
NOT re-export the function here, to avoid shadowing the submodule.
"""

from repro.core.spectral import (  # noqa: F401
    DEFAULT_STAGES,
    EigConfig,
    EmbedState,
    GraphConfig,
    GraphState,
    KMeansConfig,
    Plan,
    PipelineState,
    SpectralPipeline,
    SpectralResult,
)
from repro.core.reduce import (  # noqa: F401  (Stage 1.5 — graph reduction)
    CoarsenConfig,
    ReduceInfo,
    ReductionState,
    SparsifyConfig,
)
from repro.core.operator import (  # noqa: F401
    BlockEllOperator,
    CallableOperator,
    CooOperator,
    LinearOperator,
    ShardedCooOperator,
    TiledCooOperator,
)
from repro.core.pipeline import (  # noqa: F401  (deprecated shims)
    SpectralClusteringConfig,
    spectral_cluster,
    spectral_cluster_from_points,
)
from repro.core.lanczos import eigsh, lanczos_topk  # noqa: F401
from repro.core.kmeans import kmeanspp_init  # noqa: F401
