"""AlexNet / ImageNet workflow — BASELINE.json config 3, the driver's
target metric (samples/sec/chip).

Surface per manualrst_veles_algorithms.rst:150-164 item 6: grouped
convolution, LRN, dropout — the original 2-GPU AlexNet topology.  Run:

    python -m veles_tpu veles_tpu/samples/alexnet.py \
        veles_tpu/samples/alexnet_config.py

Real ImageNet is consumed through the directory image loader
(``root.alexnet_tpu.train_dir`` etc.); without it a synthetic
ImageNet-shaped dataset is generated (zero-egress build environment).
All convs are NHWC on the MXU; the grouped convs use XLA's native
``feature_group_count`` instead of the reference's per-group kernel
launches.
"""

import numpy

from veles_tpu.config import root
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.models.standard import StandardWorkflow


def alexnet_layers(classes=1000, dropout=0.5, space_to_depth=0,
                   side=227):
    """The canonical AlexNet layer spec (Krizhevsky et al. 2012).

    ``space_to_depth=4`` runs the 11×11/4 stem in blocked form — the
    loader pre-blocks AND stores the dataset FLAT [N, hb·wb·48]
    (4D-blocked layouts gather pathologically); the stem reshapes
    in-graph.  Numerically identical to the strided stem (exact
    parity tests); net-negative on the full step when measured in
    round 5, so it ships opt-in."""
    s2d_hw = None
    if space_to_depth:
        s2d_hw = (-(-side // space_to_depth),) * 2
    return [
        {"type": "conv_relu", "n_kernels": 96, "kx": 11, "ky": 11,
         "sliding": (4, 4), "padding": "valid",
         "space_to_depth": space_to_depth,
         "space_to_depth_hw": s2d_hw},
        {"type": "norm", "n": 5, "alpha": 1e-4, "beta": 0.75, "k": 2.0},
        {"type": "max_pooling", "kx": 3, "ky": 3, "sliding": (2, 2)},
        {"type": "conv_relu", "n_kernels": 256, "kx": 5, "ky": 5,
         "padding": 2, "n_groups": 2},
        {"type": "norm", "n": 5, "alpha": 1e-4, "beta": 0.75, "k": 2.0},
        {"type": "max_pooling", "kx": 3, "ky": 3, "sliding": (2, 2)},
        {"type": "conv_relu", "n_kernels": 384, "kx": 3, "ky": 3,
         "padding": 1},
        {"type": "conv_relu", "n_kernels": 384, "kx": 3, "ky": 3,
         "padding": 1, "n_groups": 2},
        {"type": "conv_relu", "n_kernels": 256, "kx": 3, "ky": 3,
         "padding": 1, "n_groups": 2},
        {"type": "max_pooling", "kx": 3, "ky": 3, "sliding": (2, 2)},
        {"type": "all2all_relu", "output_sample_shape": (4096,)},
        {"type": "dropout", "dropout_ratio": dropout},
        {"type": "all2all_relu", "output_sample_shape": (4096,)},
        {"type": "dropout", "dropout_ratio": dropout},
        {"type": "softmax", "output_sample_shape": (classes,)},
    ]


def vgg_a_layers(classes=1000, dropout=0.5):
    """VGG-A (extras item 6 "Last Models: AlexNet, VGG" — the
    imagenet_workflow_vgga_config surface)."""
    def conv(k):
        return {"type": "conv_relu", "n_kernels": k, "kx": 3, "ky": 3,
                "padding": 1}

    pool = {"type": "max_pooling", "kx": 2, "ky": 2}
    return [
        conv(64), pool,
        conv(128), pool,
        conv(256), conv(256), pool,
        conv(512), conv(512), pool,
        conv(512), conv(512), pool,
        {"type": "all2all_relu", "output_sample_shape": (4096,)},
        {"type": "dropout", "dropout_ratio": dropout},
        {"type": "all2all_relu", "output_sample_shape": (4096,)},
        {"type": "dropout", "dropout_ratio": dropout},
        {"type": "softmax", "output_sample_shape": (classes,)},
    ]


class ImagenetLoader(FullBatchLoader):
    """ImageNet-shaped loader: synthetic [N, 227, 227, 3] samples unless
    ``root.alexnet_tpu.train_dir`` points at a real image tree (then the
    directory image loader should be used instead — see
    veles_tpu.loader.image.FullBatchFileImageLoader).

    The synthetic dataset is drawn **on the device** (``jax.random``):
    host-side synthesis would push gigabytes through the host↔HBM link
    for data whose only purpose is to live in HBM."""

    def __init__(self, workflow, space_to_depth=None, **kwargs):
        super(ImagenetLoader, self).__init__(workflow, **kwargs)
        #: None = read root.alexnet_tpu (standalone use); the
        #: workflow passes the resolved value explicitly so loader
        #: and model cannot desync
        self.space_to_depth = space_to_depth

    def load_data(self):
        import jax
        import jax.numpy as jnp
        cfg = root.alexnet_tpu
        side = int(cfg.get("side", 227))
        classes = int(cfg.get("classes", 1000))
        n_train = int(cfg.get("synthetic_train", 2048))
        n_valid = int(cfg.get("synthetic_valid", 256))
        rng = numpy.random.default_rng(42)
        tot = n_train + n_valid
        labels = rng.integers(0, classes, tot)
        self.class_lengths[:] = [0, n_valid, n_train]
        self.original_labels = labels.tolist()
        dev = self.device.jax_device if self.device is not None else None

        s2d = int(cfg.get("space_to_depth", 0)) \
            if self.space_to_depth is None else int(self.space_to_depth)
        if s2d:
            from veles_tpu.models.conv import validate_space_to_depth
            validate_space_to_depth(side, side, 11, 11, s2d)

        @jax.jit
        def synth(key, lab):
            # stored bf16: images live in HBM only to be gathered into
            # bf16 minibatches — f32 storage doubles the gather traffic
            # and costs a whole-dataset cast every span (profiled)
            data = jax.random.uniform(key, (tot, side, side, 3),
                                      jnp.float32)
            data = data + (lab.astype(jnp.float32) / classes)[
                :, None, None, None]
            data = data.astype(jnp.bfloat16)
            if s2d:
                # pre-blocked for the space_to_depth stem (one-time,
                # at load) and stored FLAT: the per-step gather runs
                # at full rate on a 2D layout, and the stem's
                # in-graph reshape costs ~1 ms vs the ~3.5 ms the 4D
                # blocked layout lost in the span path
                from veles_tpu.models.conv import space_to_depth
                data = space_to_depth(data, s2d)
                data = data.reshape(data.shape[0], -1)
            return data

        from veles_tpu.telemetry import track_jit
        synth = track_jit("alexnet.synth_dataset", synth)
        with jax.default_device(dev):
            self.original_data = synth(
                jax.random.key(42), jnp.asarray(labels))


class AlexNetWorkflow(StandardWorkflow):
    """BASELINE config 3."""

    def __init__(self, workflow, **kwargs):
        cfg = root.alexnet_tpu
        # model = "alexnet" | "vgg_a" (the reference shipped both as
        # configs of one imagenet workflow)
        if cfg.get("model") == "vgg_a":
            s2d = 0                        # 3×3/1 stem — nothing to block
            layers = vgg_a_layers(
                classes=int(cfg.get("classes", 1000)),
                dropout=float(cfg.get("dropout", 0.5)))
        else:
            s2d = int(cfg.get("space_to_depth", 0))
            layers = alexnet_layers(
                classes=int(cfg.get("classes", 1000)),
                dropout=float(cfg.get("dropout", 0.5)),
                space_to_depth=s2d,
                side=int(cfg.get("side", 227)))
        super(AlexNetWorkflow, self).__init__(
            workflow, name="AlexNet",
            loader_factory=ImagenetLoader,
            loader_config={
                "minibatch_size": int(cfg.get("minibatch_size", 256)),
                "space_to_depth": s2d,
            },
            layers=layers,
            solver=cfg.get("solver", "sgd"),
            learning_rate=float(cfg.get("learning_rate", 0.01)),
            gradient_moment=float(cfg.get("gradient_moment", 0.9)),
            weights_decay=float(cfg.get("weights_decay", 0.0005)),
            decision_config={
                "fail_iterations": int(cfg.get("fail_iterations", 10)),
                "max_epochs": cfg.get("max_epochs"),
            },
            snapshotter_config={
                "prefix": cfg.get("snapshot_prefix", "alexnet"),
                "compression": cfg.get("snapshot_compression", "gz"),
                "time_interval":
                    float(cfg.get("snapshot_time_interval", 60.0)),
            },
            **kwargs)


def run(load, main):
    load(AlexNetWorkflow)
    main()
