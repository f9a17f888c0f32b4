"""Config registry — importing this package registers all assigned architectures."""
from repro.configs.base import (  # noqa: F401
    REGISTRY, SHAPES, ArchConfig, ShapeConfig, applicable_shapes, get_config,
)

# Assigned architectures (10) and granite-4.0-h-small — importing registers
# each into REGISTRY
# (and any chip share cut from one into SHARES).
from repro.configs import mistral_nemo_12b    # noqa: F401
from repro.configs import qwen2_5_14b         # noqa: F401
from repro.configs import command_r_35b       # noqa: F401
from repro.configs import granite_3_8b        # noqa: F401
from repro.configs import whisper_base        # noqa: F401
from repro.configs import grok_1_314b         # noqa: F401
from repro.configs import llama4_maverick_400b  # noqa: F401
from repro.configs import zamba2_2_7b         # noqa: F401
from repro.configs import internvl2_2b        # noqa: F401
from repro.configs import mamba2_1_3b         # noqa: F401
from repro.configs import granite_4_0_h_small  # noqa: F401

# The paper's own control-plane experiment config.
from repro.configs import paper_cluster       # noqa: F401

ARCH_NAMES = sorted(REGISTRY)
