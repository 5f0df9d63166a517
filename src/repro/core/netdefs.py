"""The paper's three benchmark CNNs (Table 2): LeNet-5 (MNIST),
Alex Krizhevsky's CIFAR-10 network, and AlexNet (ImageNet 2012); and
ResNet-50 v1.5, a branching network.

A network is a graph: each layer reads the tensors its ``inputs`` name
(earlier layers, or ``"input"`` for the frame), by default the layer
before it, so a chain states none."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple


@dataclass(frozen=True)
class LayerSpec:
    kind: str  # conv | pool | lrn | fc | relu | softmax | flatten | add
    name: str
    # conv/fc
    out_channels: int = 0
    kernel: Tuple[int, int] = (0, 0)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)  # conv; pool: -inf max, 0 in avg
    relu: bool = False  # fused activation (paper §4.2); add: after the sum
    #: the layers read (``"input"``: the frame); empty: the layer before
    inputs: Tuple[str, ...] = ()
    #: conv/fc: the seeded weight draw is multiplied by it
    gain: float = 1.0
    # pool
    pool_kind: str = "max"  # max | avg
    # lrn
    lrn_n: int = 5
    lrn_alpha: float = 1e-4
    lrn_beta: float = 0.75
    lrn_k: float = 1.0


@dataclass(frozen=True)
class NetworkDef:
    name: str
    input_shape: Tuple[int, int, int]  # (C, H, W)
    num_classes: int
    layers: Tuple[LayerSpec, ...]


def lenet5() -> NetworkDef:
    """LeNet-5 for MNIST [13] — Table 2 column 1."""
    return NetworkDef(
        name="lenet5",
        input_shape=(1, 28, 28),
        num_classes=10,
        layers=(
            LayerSpec("conv", "conv1", out_channels=20, kernel=(5, 5)),
            LayerSpec("pool", "pool1", kernel=(2, 2), stride=(2, 2)),
            LayerSpec("conv", "conv2", out_channels=50, kernel=(5, 5)),
            LayerSpec("pool", "pool2", kernel=(2, 2), stride=(2, 2)),
            LayerSpec("flatten", "flatten"),
            LayerSpec("fc", "fc1", out_channels=500, relu=True),
            LayerSpec("fc", "fc2", out_channels=10),
            LayerSpec("softmax", "prob"),
        ),
    )


def cifar10_quick() -> NetworkDef:
    """Krizhevsky's CIFAR-10 network [14] — Table 2 column 2."""
    return NetworkDef(
        name="cifar10",
        input_shape=(3, 32, 32),
        num_classes=10,
        layers=(
            LayerSpec("conv", "conv1", out_channels=32, kernel=(5, 5),
                      padding=(2, 2)),
            LayerSpec("pool", "pool1", kernel=(3, 3), stride=(2, 2),
                      relu=True),
            LayerSpec("conv", "conv2", out_channels=32, kernel=(5, 5),
                      padding=(2, 2), relu=True),
            LayerSpec("pool", "pool2", kernel=(3, 3), stride=(2, 2),
                      pool_kind="avg"),
            LayerSpec("conv", "conv3", out_channels=64, kernel=(5, 5),
                      padding=(2, 2), relu=True),
            LayerSpec("pool", "pool3", kernel=(3, 3), stride=(2, 2),
                      pool_kind="avg"),
            LayerSpec("flatten", "flatten"),
            LayerSpec("fc", "fc1", out_channels=64),
            LayerSpec("fc", "fc2", out_channels=10),
            LayerSpec("softmax", "prob"),
        ),
    )


def alexnet() -> NetworkDef:
    """Alex Krizhevsky's ImageNet 2012 CNN [15] (single-tower shapes,
    Fig. 8) — Table 2 column 3."""
    return NetworkDef(
        name="alexnet",
        input_shape=(3, 227, 227),
        num_classes=1000,
        layers=(
            LayerSpec("conv", "conv1", out_channels=96, kernel=(11, 11),
                      stride=(4, 4), relu=True),
            LayerSpec("pool", "pool1", kernel=(3, 3), stride=(2, 2)),
            LayerSpec("lrn", "norm1"),
            LayerSpec("conv", "conv2", out_channels=256, kernel=(5, 5),
                      padding=(2, 2), relu=True),
            LayerSpec("pool", "pool2", kernel=(3, 3), stride=(2, 2)),
            LayerSpec("lrn", "norm2"),
            LayerSpec("conv", "conv3", out_channels=384, kernel=(3, 3),
                      padding=(1, 1), relu=True),
            LayerSpec("conv", "conv4", out_channels=384, kernel=(3, 3),
                      padding=(1, 1), relu=True),
            LayerSpec("conv", "conv5", out_channels=256, kernel=(3, 3),
                      padding=(1, 1), relu=True),
            LayerSpec("pool", "pool5", kernel=(3, 3), stride=(2, 2)),
            LayerSpec("flatten", "flatten"),
            LayerSpec("fc", "fc6", out_channels=4096, relu=True),
            LayerSpec("fc", "fc7", out_channels=4096, relu=True),
            LayerSpec("fc", "fc8", out_channels=1000),
            LayerSpec("softmax", "prob"),
        ),
    )


def resnet50() -> NetworkDef:
    """ResNet-50 v1.5 (torchvision ``resnet50``, MLPerf Inference's
    classifier): the stride on each stage's first 3×3 conv, batch norm
    folded into the convs' weights and biases.  Each bottleneck ``_a``
    1×1 → ``_b`` 3×3 → ``_c`` 1×1 ends in ``_add``, the sum with the
    block's input (or with its strided 1×1 projection ``_proj``, on each
    stage's first block) and a ReLU.  ``gain=0.2`` on every ``_c`` keeps
    seeded random logits near unit scale (with He draws alone their
    second moment grows about 2.4× per block)."""
    def conv(name, src, out, k, stride=1, relu=True, gain=1.0):
        return LayerSpec("conv", name, out_channels=out, kernel=(k, k),
                         stride=(stride, stride),
                         padding=(k // 2, k // 2), relu=relu,
                         inputs=(src,), gain=gain)

    layers = [conv("conv1", "input", 64, 7, stride=2),
              LayerSpec("pool", "pool1", kernel=(3, 3), stride=(2, 2),
                        padding=(1, 1), inputs=("conv1",))]
    x = "pool1"
    for stage, (width, blocks) in enumerate(((64, 3), (128, 4), (256, 6),
                                             (512, 3)), start=1):
        for block in range(1, blocks + 1):
            p = f"s{stage}b{block}_"
            stride = 2 if stage > 1 and block == 1 else 1
            layers += [conv(p + "a", x, width, 1),
                       conv(p + "b", p + "a", width, 3, stride=stride),
                       conv(p + "c", p + "b", 4 * width, 1, relu=False,
                            gain=0.2)]
            shortcut = x
            if block == 1:
                shortcut = p + "proj"
                layers.append(conv(shortcut, x, 4 * width, 1,
                                   stride=stride, relu=False))
            layers.append(LayerSpec("add", p + "add", relu=True,
                                    inputs=(p + "c", shortcut)))
            x = p + "add"
    layers += [
        LayerSpec("pool", "pool5", kernel=(7, 7), stride=(1, 1),
                  pool_kind="avg", inputs=(x,)),
        LayerSpec("flatten", "flatten", inputs=("pool5",)),
        LayerSpec("fc", "fc1000", out_channels=1000, inputs=("flatten",)),
        LayerSpec("softmax", "prob", inputs=("fc1000",)),
    ]
    return NetworkDef(name="resnet50", input_shape=(3, 224, 224),
                      num_classes=1000, layers=tuple(layers))


def layer_inputs(net: NetworkDef) -> Dict[str, Tuple[str, ...]]:
    """Each layer's inputs, resolved: its own ``inputs``, else the layer
    before it (``"input"`` for the first)."""
    out, prev = {}, "input"
    for spec in net.layers:
        out[spec.name] = spec.inputs or (prev,)
        prev = spec.name
    return out


def layer_readers(net: NetworkDef) -> Dict[str, List[str]]:
    """The layers that read each tensor (``"input"`` and layer names),
    in network order; the last layer's output is read by the caller and
    counts no reader here."""
    readers: Dict[str, List[str]] = {}
    for name, ins in layer_inputs(net).items():
        for src in ins:
            readers.setdefault(src, []).append(name)
    return readers


NETWORKS = {"lenet5": lenet5, "cifar10": cifar10_quick, "alexnet": alexnet,
            "resnet50": resnet50}
