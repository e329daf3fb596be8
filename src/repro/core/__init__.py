"""VSCNN core: vector sparsity as a first-class JAX feature.

- `VectorSparse`       balanced block-CSR weight format (paper's index system)
- `pruning`            Mao-style vector pruning (global + balanced)
- `sparse_ops`         structural sparse matmul/conv (jnp + Pallas dispatch)
- `accel_model`        cycle-accurate PE-array simulator (paper Table I/Figs 12-13)
- `calibration`        measured-vs-modeled loop: per-layer wall-clock + HLO
                       cost features, fitted model constants, CI drift gate
"""
from .vector_sparse import (
    VectorSparse, encode, decode, from_mask, tile_mask, conv_cin_major,
)
from .pruning import (
    prune_vectors,
    prune_vectors_balanced,
    prune_conv_columns,
    prune_tree_balanced,
    element_density,
)
from .sparse_ops import (
    vs_matmul,
    vs_conv2d,
    vs_conv2d_3x3,
    dense_conv2d,
    dense_conv2d_3x3,
    im2col,
    im2col_3x3,
    s2d_im2col,
    s2d_weight_matrix,
    conv_weight_to_matrix,
    same_pads,
)
from .accel_model import (
    PEConfig,
    PE_4_14_3,
    PE_8_7_3,
    CycleReport,
    TrafficReport,
    conv_layer_cycles,
    conv_layer_traffic,
    aggregate,
    network_cycle_reports,
    network_traffic_reports,
    table1_example,
    load_calibration,
    predicted_layer_time_s,
)
from .calibration import (
    CalibConstants,
    fit_constants,
    predict_time_s,
    compare_calibration,
    measured_vs_modeled_records,
)
