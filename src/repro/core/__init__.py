"""The paper's contribution: domain-decomposed model parallelism for FNOs.

Public surface:
  * ``CartPartition`` / ``repartition`` — DistDL-style partition + R_{x->y}
  * ``dfft`` — distributed truncated 4-D FFT (Alg. 2 operators + adjoints)
  * ``FNOConfig`` / ``fno_forward`` / ``forward_and_specs`` — the FNO and
    its forward for a mesh: serial, paper Alg. 2 slabs or 2-D pencils
  * ``make_pipeline_forward`` — GPipe baseline the paper compares against
  * ``ulysses_attention`` — the repartition primitive applied to attention
"""
from repro.core.partition import CartPartition, make_mesh  # noqa: F401
from repro.core.repartition import (  # noqa: F401
    repartition,
    repartition_chunked,
    repartition_multi,
    repartition_multi_t,
    repartition_t,
)
from repro.core.fno import (  # noqa: F401
    FNOConfig,
    contrib_spec,
    encoder_prelift,
    fno_forward,
    forward_and_specs,
    init_params,
    input_spec,
    mse_loss,
    param_specs,
    params_with_planes,
    params_without_planes,
    spectral_prelift,
)
from repro.core.pipeline import bubble_efficiency, make_pipeline_forward  # noqa: F401
from repro.core.ulysses import ulysses_attention  # noqa: F401
