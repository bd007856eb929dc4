"""Core library: the paper's contribution as composable JAX modules."""
from .lif import LIFParams, lif_scan, lif_step, spike_surrogate, leaky_integrate
from .coding import direct_code, rate_code_images, spike_count, sparsity
from .quant import QTensor, fake_quant, quant_levels, quantize_int4, dequantize, pack_int4, unpack_int4, qat_params
from .sparsity import SpikeStats, tile_occupancy
from .workload import (
    LayerWorkload,
    balance_allocation,
    conv_workload,
    dense_input_workload,
    fc_workload,
    layer_latencies,
    latency_overheads,
    scale_allocation,
)
from .energy import (
    PEAK_FLOPS_BF16,
    HBM_BW,
    ICI_BW,
    RooflineTerms,
    roofline,
    energy_per_image,
    power_model,
)
from .hybrid import (HybridPlan, KernelSpec, LayerPlan, plan_hybrid,
                     plan_vgg9_inference)
