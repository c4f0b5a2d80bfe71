"""Plain reference of `resnet18-cifar-f32`: the CIFAR-adapted BasicBlock
ResNet of He et al. 2015 — a 3x3 stem (no 7x7, no max-pool), stages of
BasicBlocks (two 3x3 convolutions without bias, each with BatchNorm; a 1x1
convolution + BatchNorm on the shortcut where stride or width changes),
global average pool, Linear(512, 10).  Widths, strides and block counts are
read from the configuration's `stages` and `blocks`.
"""

import jax
import jax.numpy as jnp

from benchmark.reference import common as ref


def make(config):
    stages = [tuple(s) for s in config["stages"]]
    counts = config["blocks"]

    def block_init(key, cin, cout, stride):
        k1, k2, k3 = jax.random.split(key, 3)
        p, s = {}, {}
        p["conv1"] = ref.conv_init(k1, cin, cout, 3, bias=False)
        p["bn1"], s["bn1"] = ref.bn_init(cout)
        p["conv2"] = ref.conv_init(k2, cout, cout, 3, bias=False)
        p["bn2"], s["bn2"] = ref.bn_init(cout)
        if stride != 1 or cin != cout:
            p["down_conv"] = ref.conv_init(k3, cin, cout, 1, bias=False)
            p["down_bn"], s["down_bn"] = ref.bn_init(cout)
        return p, s

    def init(key):
        key, sub = jax.random.split(key)
        params = {"stem_conv": ref.conv_init(sub, 3, stages[0][0], 3,
                                             bias=False)}
        state = {}
        params["stem_bn"], state["stem_bn"] = ref.bn_init(stages[0][0])
        cin = stages[0][0]
        bp, bs = [], []
        for (width, stage_stride), n in zip(stages, counts):
            for b in range(n):
                key, sub = jax.random.split(key)
                p, s = block_init(sub, cin, width,
                                  stage_stride if b == 0 else 1)
                bp.append(p)
                bs.append(s)
                cin = width
        params["blocks"], state["blocks"] = bp, bs
        key, sub = jax.random.split(key)
        fin, fout = config["classifier"]
        params["fc"] = ref.linear_init(sub, fin, fout)
        return params, state

    def block_apply(p, s, x, stride, train):
        ns = {}
        y = ref.conv(p["conv1"], x, stride=stride)
        y, ns["bn1"] = ref.batchnorm(p["bn1"], s["bn1"], y, train)
        y = ref.relu(y)
        y = ref.conv(p["conv2"], y)
        y, ns["bn2"] = ref.batchnorm(p["bn2"], s["bn2"], y, train)
        sc = x
        if "down_conv" in p:
            sc = ref.conv(p["down_conv"], x, stride=stride, padding=0)
            sc, ns["down_bn"] = ref.batchnorm(p["down_bn"], s["down_bn"],
                                              sc, train)
        return ref.relu(y + sc), ns

    def apply(params, state, x, train):
        ns = {}
        y = ref.conv(params["stem_conv"], x)
        y, ns["stem_bn"] = ref.batchnorm(params["stem_bn"], state["stem_bn"],
                                         y, train)
        y = ref.relu(y)
        blocks = []
        i = 0
        for (width, stage_stride), n in zip(stages, counts):
            for b in range(n):
                y, s = block_apply(params["blocks"][i], state["blocks"][i],
                                   y, stage_stride if b == 0 else 1, train)
                blocks.append(s)
                i += 1
        ns["blocks"] = blocks
        y = jnp.mean(y, axis=(1, 2))
        return ref.linear(params["fc"], y), ns

    return init, apply
