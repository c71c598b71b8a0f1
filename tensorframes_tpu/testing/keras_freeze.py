"""Freeze a production Keras architecture into GraphDef bytes.

Used by `tests/test_foreign_graphdef.py` (the Keras-zoo conformance
tests) and by `chip_smoke.py` phase f (Inception-v3 on the chip), so the
graph the chip scores is built by the same recipe as the graph the
tests validate. Requires TensorFlow, an optional tool here and never a
runtime dependency; importing this module does not import it.
"""

from __future__ import annotations

import os


def freeze_keras_model(ctor_name: str, input_hw: int):
    """Build a PRODUCTION Keras architecture (`tf.keras.applications.
    <ctor_name>`) and freeze it with TF2's
    `convert_variables_to_constants_v2` — the modern form of the
    reference demo's freeze (`read_image.py:111-124`). The multi-MB
    graphs are shaped entirely by Keras, not by this repo. Weights are
    seeded-random: the environment has zero egress and no cached
    pretrained checkpoints, so `weights="imagenet"` cannot be
    satisfied — prediction agreement vs a TF session is checked instead
    (`tests/test_foreign_graphdef.py`), which is weight-independent
    evidence of correct ingestion/lowering.

    The ONE freeze recipe, shared by `chip_smoke.py` phase f and every
    model-zoo conformance test, so the graph scored on the chip is
    byte-identical to the graph validated. Requires TensorFlow (an
    optional tool here, never a runtime dep); raises ImportError where
    it is absent.

    Returns (graph_bytes, input_node, output_node, tf_score_fn)."""
    os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
    import tensorflow as tf

    tf.keras.utils.set_random_seed(7)
    model = getattr(tf.keras.applications, ctor_name)(
        weights=None, input_shape=(input_hw, input_hw, 3)
    )
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2,
    )

    fn = tf.function(lambda x: model(x, training=False))
    cf = fn.get_concrete_function(
        tf.TensorSpec([None, input_hw, input_hw, 3], tf.float32)
    )
    frozen = convert_variables_to_constants_v2(cf)
    gd = frozen.graph.as_graph_def()

    def score(images):
        out = frozen(tf.constant(images))
        if isinstance(out, (list, tuple)):
            out = out[0]
        return out.numpy()

    return (
        gd.SerializeToString(),
        frozen.inputs[0].name.split(":")[0],
        frozen.outputs[0].name.split(":")[0],
        score,
    )


def freeze_keras_inception_v3(input_hw: int):
    """BASELINE config 5's model, through the shared recipe."""
    return freeze_keras_model("InceptionV3", input_hw)
