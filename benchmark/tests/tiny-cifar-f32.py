"""Plain reference of tests/tinynet.py's tiny_cnn, for the CPU rehearsal:
conv(3->8, bias) + BatchNorm + ReLU + two 2x2 max-pools + Linear(512, 10)."""

import jax

from benchmark.reference import common as ref


def make(config):
    def init(key):
        k1, k2 = jax.random.split(key)
        params = {"conv": ref.conv_init(k1, 3, 8, 3)}
        params["bn"], bn = ref.bn_init(8)
        params["fc"] = ref.linear_init(k2, 8 * 8 * 8, 10)
        return params, {"bn": bn}

    def apply(params, state, x, train):
        y = ref.conv(params["conv"], x)
        y, bn = ref.batchnorm(params["bn"], state["bn"], y, train)
        y = ref.maxpool2x2(ref.maxpool2x2(ref.relu(y)))
        return ref.linear(params["fc"], y.reshape(y.shape[0], -1)), {"bn": bn}

    return init, apply
