"""Model zoo: framework-native models in both forms (trainable JAX +
frozen GraphDef-compatible scoring graphs), and `lm`: a language model
built from a published configuration, scored through the verbs with its
weights bound as device-resident arguments."""

from . import lm, moe
from .inception import InceptionLite
from .kmeans import kmeans
from .mlp import MLP
from .moe import MoEFFN, held_experts, route
from .training import init_opt_state, make_train_step
from .transformer import TransformerLM

__all__ = [
    "MLP", "kmeans", "TransformerLM", "InceptionLite", "MoEFFN", "lm", "moe",
    "route", "held_experts", "make_train_step", "init_opt_state",
]
