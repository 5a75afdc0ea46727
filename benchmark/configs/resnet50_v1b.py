"""Builder and work counters of `resnet50_v1b`: the program's model-zoo
`resnet50_v1b` under `parallel.ShardedTrainer` over a data mesh of the
cell's chips (zero=0, amp bfloat16): one program across the chips, with
the gradient all-reduce and BatchNorm's global statistics inside it.
"""
from __future__ import annotations

import gc


def _bn(out, pre, blk):
    out[pre + ".g"] = blk.gamma
    out[pre + ".b"] = blk.beta
    out[pre + ".rm"] = blk.running_mean
    out[pre + ".rv"] = blk.running_var


def param_map(net):
    """{reference name: program Parameter}."""
    feats = list(net.features._children.values())
    out = {"stem.conv.w": feats[0].weight}
    _bn(out, "stem.bn", feats[1])
    for s, stage in enumerate(feats[4:8]):
        for b, blk in enumerate(stage._children.values()):
            p = "s%d.b%d" % (s, b)
            body = list(blk.body._children.values())
            out[p + ".conv1.w"] = body[0].weight
            _bn(out, p + ".bn1", body[1])
            out[p + ".conv2.w"] = body[3].weight
            _bn(out, p + ".bn2", body[4])
            out[p + ".conv3.w"] = body[6].weight
            _bn(out, p + ".bn3", body[7])
            if blk.downsample is not None:
                down = list(blk.downsample._children.values())
                out[p + ".down.conv.w"] = down[0].weight
                _bn(out, p + ".down.bn", down[1])
    out["fc.w"] = net.output.weight
    out["fc.b"] = net.output.bias
    return out


class ShardedSystem:
    """The system under test: `ShardedTrainer.step` on a resident batch."""

    kind = "train"

    def __init__(self, trainer, names, feed, items, lr):
        self.trainer = trainer
        self.names = names          # reference name -> program param name
        self.feed = feed
        self.items_per_step = items
        self._lr = lr

    def step(self):
        return self.trainer.step(*self.feed)

    def wait(self, handle):
        import jax
        jax.block_until_ready(handle)

    def fence(self):
        import jax
        jax.block_until_ready(self.trainer.params)

    def loss_value(self, handle):
        import numpy as np
        return float(np.asarray(handle))

    def params(self):
        return {k: self.trainer.params[n] for k, n in self.names.items()}

    def first_gradients(self):
        """The first gradient as the optimizer got it (g + wd w), from the
        momentum buffer after one step: m1 = -lr (g + wd w)."""
        return {k: self.trainer.opt_state[n] * (-1.0 / self._lr)
                for k, n in self.names.items()
                if not k.endswith((".rm", ".rv"))}

    def close(self):
        from incubator_mxnet_tpu.contrib import amp
        self.trainer.release()
        amp.turn_off()
        self.trainer = self.feed = None
        gc.collect()


def build(config, traffic, weights, batch, devices, ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from incubator_mxnet_tpu import nd, parallel
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    opt = config["training"]
    if len(devices) != int(np.prod(opt["mesh"])):
        raise ValueError("the mesh %s needs %d devices, the cell has %d"
                         % (opt["mesh"], int(np.prod(opt["mesh"])), len(devices)))
    side = int(traffic["image_side"])
    net = vision.resnet50_v1b(classes=config["num_classes"])
    net.initialize(ctx=ctx)
    net(nd.array(np.zeros((2, 3, side, side), np.float32), ctx=ctx))
    pmap = param_map(net)
    if set(pmap) != set(weights) or len(pmap) != len(net.collect_params()):
        raise ValueError("weights and program parameters differ: %s"
                         % sorted(set(pmap) ^ set(weights))[:8])
    for name, param in pmap.items():
        param.set_data(nd.NDArray(weights[name], ctx=ctx))
    mesh = parallel.make_mesh(tuple(opt["mesh"]), tuple(opt["mesh_axes"]),
                              devices=list(devices))
    trainer = parallel.ShardedTrainer(
        net, optimizer=opt["optimizer"], lr=opt["learning_rate"],
        momentum=opt["momentum"], wd=opt["weight_decay"], mesh=mesh, zero=0,
        amp=config["compute_dtype"])
    sharding = parallel.batch_sharded(mesh)
    x = jax.device_put(batch["images"], sharding).astype(
        jnp.dtype(traffic["input_dtype"]))
    y = jax.device_put(batch["labels"], sharding)
    names = {k: p.name for k, p in pmap.items()}
    return ShardedSystem(trainer, names, (x, y), batch["items_per_step"],
                         opt["learning_rate"])


def reference_place(weights, batch, devices):
    """Weights replicated and the batch sharded over the cell's chips, so
    that the plain reference's float32 activations fit."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(list(devices)), ("data",))
    w = jax.device_put(weights, NamedSharding(mesh, P()))
    b = {"images": jax.device_put(batch["images"], NamedSharding(mesh, P("data"))),
         "labels": jax.device_put(batch["labels"], NamedSharding(mesh, P("data")))}
    return w, b


# ---- work the algorithm needs, from shapes ------------------------------

def _conv_macs(cin, cout, k, out_side):
    return cin * cout * k * k * out_side * out_side


def forward_flops(config, side):
    """Multiply-adds x 2 of the convolutions and the classifier for one
    image (BatchNorm, ReLU and pooling not counted)."""
    s = side // 2
    macs = _conv_macs(3, 64, 7, s)
    s //= 2
    cin = 64
    for i, (blocks, ch) in enumerate(zip(config["stage_blocks"],
                                         config["stage_widths"])):
        mid = ch // config["bottleneck_expansion"]
        for b in range(blocks):
            stride = 2 if (b == 0 and i > 0) else 1
            macs += _conv_macs(cin, mid, 1, s)
            s_out = s // stride
            macs += _conv_macs(mid, mid, 3, s_out)
            macs += _conv_macs(mid, ch, 1, s_out)
            if b == 0:
                macs += _conv_macs(cin, ch, 1, s_out)
            s, cin = s_out, ch
    macs += cin * config["num_classes"]
    return 2 * macs


def step_flops(config, traffic):
    """Forward + backward (3 x forward) of one global step."""
    return 3 * forward_flops(config, traffic["image_side"]) * traffic["batch"]
