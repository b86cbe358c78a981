# The paper's primary contribution: submodular functions, submodular
# information measures, and greedy maximizers — vectorized for TPU and
# distributable over a multi-pod mesh (see DESIGN.md §2, §5).
from repro.core.functions.base import SetFunction
from repro.core.functions.clustered import (
    cluster_mask,
    clustered,
    clustered_matrix_free,
)
from repro.core.functions.disparity import DisparityMin, DisparityMinSum, DisparitySum
from repro.core.functions.facility_location import FacilityLocation, FacilityLocationMF
from repro.core.functions.feature_based import FeatureBased
from repro.core.functions.graph_cut import GraphCut, GraphCutMF
from repro.core.functions.log_det import LogDet
from repro.core.functions.set_cover import ProbabilisticSetCover, SetCover
from repro.core.info.com import ConcaveOverModular
from repro.core.info.combinators import (
    ConditionedFunction,
    DifferenceFunction,
    generic_cg,
    generic_cmi,
    generic_mi,
)
from repro.core.info.fl import FLCG, FLCMI, FLQMI, FLVMI
from repro.core.info.gc import GCMI, gccg, gccmi
from repro.core.info.logdet import logdet_cg, logdet_cmi, logdet_mi
from repro.core.info.sc import psc_cg, psc_cmi, psc_mi, sc_cg, sc_cmi, sc_mi
from repro.core.optimizers.api import maximize
from repro.core.optimizers.spec import (
    OptimizerSpec,
    SelectionSpec,
    family_defaults,
    optimizer_names,
    register_family_defaults,
    register_optimizer,
    resolve_optimizer,
    solve,
    wave_capable_names,
)
from repro.core.optimizers.backends import (
    GainBackend,
    full_sweep,
    register_gain_backend,
    resolve_backend,
)
from repro.core.optimizers.batched import (
    BatchedEngine,
    batched_maximize,
    stack_functions,
)
from repro.core.optimizers.constrained import (
    Knapsack,
    PartitionMatroid,
    cover_greedy,
    knapsack_greedy,
    matroid_greedy,
)
from repro.core.optimizers.distributed import (
    register_shard_rule,
    shard_rule,
    sharded_batched_greedy,
    stack_parts,
)
from repro.core.optimizers.greedy import (
    GreedyResult,
    lazier_than_lazy_greedy,
    lazy_greedy,
    naive_greedy,
    stochastic_greedy,
)
from repro.core.optimizers.host_lazy import host_lazy_greedy
from repro.core.optimizers.streaming import sieve_streaming, threshold_greedy
from repro.core.similarity import (
    build_extended_kernel,
    create_kernel,
    kmeans,
    sparsify_topk,
)
from repro.core.sources import (
    DenseSource,
    FeatureSource,
    KnnSource,
    dense_source,
    feature_source,
    knn_from_features,
    knn_source,
)

__all__ = [
    "SetFunction",
    "FacilityLocation",
    "GraphCut",
    "LogDet",
    "SetCover",
    "ProbabilisticSetCover",
    "FeatureBased",
    "DisparitySum",
    "DisparityMin",
    "DisparityMinSum",
    "ConcaveOverModular",
    "clustered",
    "cluster_mask",
    "clustered_matrix_free",
    "FacilityLocationMF",
    "GraphCutMF",
    "FeatureSource",
    "KnnSource",
    "DenseSource",
    "feature_source",
    "knn_source",
    "knn_from_features",
    "dense_source",
    "FLVMI",
    "FLQMI",
    "FLCG",
    "FLCMI",
    "GCMI",
    "gccg",
    "gccmi",
    "logdet_mi",
    "logdet_cg",
    "logdet_cmi",
    "sc_mi",
    "sc_cg",
    "sc_cmi",
    "psc_mi",
    "psc_cg",
    "psc_cmi",
    "generic_mi",
    "generic_cg",
    "generic_cmi",
    "ConditionedFunction",
    "DifferenceFunction",
    "SelectionSpec",
    "OptimizerSpec",
    "solve",
    "register_optimizer",
    "register_family_defaults",
    "optimizer_names",
    "resolve_optimizer",
    "wave_capable_names",
    "family_defaults",
    "maximize",
    "batched_maximize",
    "BatchedEngine",
    "stack_functions",
    "GainBackend",
    "register_gain_backend",
    "resolve_backend",
    "full_sweep",
    "naive_greedy",
    "lazy_greedy",
    "stochastic_greedy",
    "lazier_than_lazy_greedy",
    "host_lazy_greedy",
    "cover_greedy",
    "knapsack_greedy",
    "matroid_greedy",
    "Knapsack",
    "PartitionMatroid",
    "sieve_streaming",
    "threshold_greedy",
    "sharded_batched_greedy",
    "shard_rule",
    "register_shard_rule",
    "stack_parts",
    "GreedyResult",
    "create_kernel",
    "build_extended_kernel",
    "sparsify_topk",
    "kmeans",
]
