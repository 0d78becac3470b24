"""Host runtime: distributed bring-up, meshes, symmetric buffers, perf utils.

TPU-native analog of the reference's host runtime
(ref: python/triton_dist/utils.py:182-205 `initialize_distributed`,
:114-176 symmetric tensors, :274-318 perf/printing).
"""

from triton_dist_tpu.runtime.init import (  # noqa: F401
    enable_compile_cache,
    initialize_distributed,
    finalize_distributed,
    get_default_mesh,
    set_default_mesh,
    make_mesh,
    split_mesh,
    rank,
    num_ranks,
    init_seed,
    TP_AXIS,
    EP_AXIS,
    SP_AXIS,
    PP_AXIS,
    DP_AXIS,
)
from triton_dist_tpu.runtime.symm_mem import (  # noqa: F401
    symm_tensor,
    symm_zeros,
    SymmetricWorkspace,
)
from triton_dist_tpu.runtime.utils import (  # noqa: F401
    dist_print,
    perf_func,
    chain_timer,
    assert_allclose,
    group_profile,
    merge_traces,
)
from triton_dist_tpu.runtime.topology import (  # noqa: F401
    Topology,
    discover_topology,
    measure_axis_bandwidth,
)
