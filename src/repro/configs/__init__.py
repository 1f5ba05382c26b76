from repro.configs.registry import ARCHS, get_config, smoke_config  # noqa: F401
from repro.configs.cnn import (  # noqa: F401
    CNN_ARCHS,
    cnn_model,
    get_cnn_config,
    smoke_cnn_config,
)
from repro.configs.shapes import SHAPES, cell_runnable, input_specs, make_batch  # noqa: F401
