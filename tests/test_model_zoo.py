"""Model zoo structural test — the reference's benchmark model family.

Parity model: the reference benches ResNet via keras.applications /
torchvision; here the flax implementation is checked for its canonical
parameter count (ImageNet config).
"""

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu import models


def _param_count(params):
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


def test_resnet50_imagenet_param_count():
    m = models.ResNet50(num_classes=1000, dtype=jnp.float32)
    variables = jax.eval_shape(
        lambda: m.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 224, 224, 3)), train=False))
    params = variables["params"]
    n = _param_count(params)
    # torchvision resnet50: 25,557,032 (incl. fc); BN stats excluded here
    assert 25.0e6 < n < 26.0e6, n
