"""Architecture configs.

Importing this package registers every assigned architecture (from the
public pool) plus the paper's own CNN testbed (VGG16/19, ResNet50/101) in
``repro.config.registry``. Select with ``--arch <id>`` in the launchers.
"""
from repro.configs import (  # noqa: F401
    cnn_testbed,
    granite_34b,
    grok_1_314b,
    llama4_maverick_400b_a17b,
    moonlight_16b_a3b,
    olmo_1b,
    qwen2_vl_7b,
    qwen3_8b,
    seamless_m4t_large_v2,
    xlstm_1_3b,
    yi_6b,
    zamba2_2_7b,
)
