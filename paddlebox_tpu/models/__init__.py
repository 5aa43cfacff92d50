from paddlebox_tpu.models.layers import mlp_init, mlp_apply, linear_init, linear_apply
from paddlebox_tpu.models.lr import LogisticRegression
from paddlebox_tpu.models.deepfm import DeepFM
from paddlebox_tpu.models.wide_deep import WideDeep, DCN
from paddlebox_tpu.models.mmoe import MMoE, task_head
from paddlebox_tpu.models.rank import RankDeepFM
from paddlebox_tpu.models.glm_moe_lite import GlmMoeLite, GlmMoeLiteConfig
from paddlebox_tpu.models.afmoe import Afmoe, AfmoeConfig
from paddlebox_tpu.models.smallthinker import SmallThinker, SmallThinkerConfig
from paddlebox_tpu.models.sdar import Sdar, SdarConfig
from paddlebox_tpu.models.xing4 import Xing4, Xing4Config
from paddlebox_tpu.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig

__all__ = [
    "mlp_init",
    "mlp_apply",
    "linear_init",
    "linear_apply",
    "LogisticRegression",
    "DeepFM",
    "WideDeep",
    "DCN",
    "MMoE",
    "task_head",
    "RankDeepFM",
    "GlmMoeLite",
    "GlmMoeLiteConfig",
    "Afmoe",
    "AfmoeConfig",
    "SmallThinker",
    "SmallThinkerConfig",
    "Sdar",
    "SdarConfig",
    "Xing4",
    "Xing4Config",
    "OlmoHybrid",
    "OlmoHybridConfig",
]
