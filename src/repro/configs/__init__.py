"""Architecture registry — one module per assigned architecture.

Importing this package registers every config; ``--arch <id>`` resolves via
``repro.models.config.get_config``.
"""
from repro.configs import (  # noqa: F401
    qwen2_5_14b,
    qwen3_32b,
    grok_1_314b,
    starcoder2_7b,
    llama4_scout_17b_a16e,
    h2o_danube_3_4b,
    whisper_small,
    rwkv6_1_6b,
    qwen2_vl_72b,
    recurrentgemma_2b,
    deepseek_v2_lite,
    paper_models,
)

ASSIGNED = (
    "qwen2.5-14b",
    "qwen3-32b",
    "grok-1-314b",
    "starcoder2-7b",
    "llama4-scout-17b-a16e",
    "h2o-danube-3-4b",
    "whisper-small",
    "rwkv6-1.6b",
    "qwen2-vl-72b",
    "recurrentgemma-2b",
    "deepseek-v2-lite",
)
