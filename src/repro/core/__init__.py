"""repro.core — the paper's contribution: PCILT.

Pre-Calculated Inference Lookup Tables (Gatchev & Mollov, 2021): with
low-cardinality activations, pre-compute every possible convolution partial
product into tables and *fetch* at inference time instead of multiplying.

Submodules: quantization (code grids + STE), offsets (activation->offset
packing, ext. 1), pcilt (table builders, ext. 2/3), lut_layers (inference
layers with gather / one-hot-MXU / Pallas paths), learnable (ext. 4).
"""

from .quantization import (
    QuantSpec,
    calibrate,
    scale_from_amax,
    quantize,
    quantize_with_stats,
    dequantize,
    fake_quant,
    code_values,
)
from .offsets import pack_offsets, unpack_offsets, offset_grid, SegmentPlan
from .pcilt import (
    mul_fn,
    log_mul_fn,
    build_scalar_tables,
    build_grouped_tables,
    SharedTables,
    build_shared_tables,
    SharedGroupedTables,
    build_shared_grouped_tables,
    ShardedSharedPool,
    shard_shared_grouped_tables,
    table_bytes,
    grouped_table_bytes,
    shared_table_bytes,
    shared_pool_bytes,
    build_cost_multiplies,
    table_checksum,
    table_checksums,
    slice_checksums,
    stacked_checksums,
)
from .lut_layers import (
    lut_lookup,
    pcilt_linear,
    pcilt_conv2d,
    pcilt_depthwise_conv1d,
    build_dwconv_tables,
    im2col,
    conv_same_pads,
    mesh_shard_count,
)
from .learnable import (
    init_learnable_pcilt,
    apply_learnable_pcilt,
    effective_tables,
    extract_filters,
)
