"""Plain reference of `vgg11-cifar-f32`: VGG-11 with BatchNorm for 32x32
inputs — 3x3 convolutions (padding 1, bias) each followed by BatchNorm and
ReLU, 2x2 max-pool at every "M", then Linear(512, 10).  The widths are read
from the configuration's `cfg`.  The parameter tree keeps the layout the
configuration's users see: {"conv": [...], "bn": [...], "fc1": {...}}.
"""

import jax

from benchmark.reference import common as ref


def make(config):
    cfg = config["cfg"]

    def init(key):
        conv, bn_p, bn_s = [], [], []
        cin = 3
        for c in cfg:
            if c == "M":
                continue
            key, sub = jax.random.split(key)
            conv.append(ref.conv_init(sub, cin, c, 3))
            p, s = ref.bn_init(c)
            bn_p.append(p)
            bn_s.append(s)
            cin = c
        key, sub = jax.random.split(key)
        fin, fout = config["classifier"]
        return ({"conv": conv, "bn": bn_p,
                 "fc1": ref.linear_init(sub, fin, fout)}, {"bn": bn_s})

    def apply(params, state, x, train):
        new_bn = []
        i = 0
        for c in cfg:
            if c == "M":
                x = ref.maxpool2x2(x)
                continue
            x = ref.conv(params["conv"][i], x)
            x, s = ref.batchnorm(params["bn"][i], state["bn"][i], x, train)
            new_bn.append(s)
            x = ref.relu(x)
            i += 1
        x = x.reshape(x.shape[0], -1)
        return ref.linear(params["fc1"], x), {"bn": new_bn}

    return init, apply
