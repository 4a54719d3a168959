"""The configurations hold the published shapes, and the least bytes of a
round are what a hand count gives."""
import json
import math

import pytest

from chipbench import roofline
from chipbench.plan import REPO


def _config(name):
    return json.loads((REPO / "chipbench" / "configs" / f"{name}.json")
                      .read_text())


def _make_divisible(v, d=8):
    """torchvision's channel rounding (from the TensorFlow reference)."""
    nv = max(d, int(v + d / 2) // d * d)
    return nv + d if nv < 0.9 * v else nv


def vgg16_leaves(classes=1000):
    """Configuration D (Simonyan and Zisserman 2014), torchvision order."""
    out, cin = [], 3
    for b, widths in enumerate([[64] * 2, [128] * 2, [256] * 3, [512] * 3,
                                [512] * 3], start=1):
        for i, w in enumerate(widths, start=1):
            out += [[f"conv{b}_{i}.w", [3, 3, cin, w]],
                    [f"conv{b}_{i}.b", [w]]]
            cin = w
    for name, n_in, n_out in (("fc6", 512 * 7 * 7, 4096),
                              ("fc7", 4096, 4096), ("fc8", 4096, classes)):
        out += [[f"{name}.w", [n_in, n_out]], [f"{name}.b", [n_out]]]
    return out


def effnetb7_leaves(width=2.0, depth=3.1, classes=1000):
    """The B0 stage table (Tan and Le 2019) scaled as torchvision does."""
    table = [(1, 3, 32, 16, 1), (6, 3, 16, 24, 2), (6, 5, 24, 40, 2),
             (6, 3, 40, 80, 3), (6, 5, 80, 112, 3), (6, 5, 112, 192, 4),
             (6, 3, 192, 320, 1)]
    c0 = _make_divisible(32 * width)
    out = [["stem.conv", [3, 3, 3, c0]], ["stem.bn.scale", [c0]],
           ["stem.bn.bias", [c0]]]
    nb = 0
    for expand, k, c_in, c_out, n in table:
        ci, co = _make_divisible(c_in * width), _make_divisible(c_out * width)
        for b in range(int(math.ceil(n * depth))):
            cin = ci if b == 0 else co
            ex, sq, p = _make_divisible(cin * expand), max(1, cin // 4), f"b{nb}"
            if ex != cin:
                out += [[p + ".expand.conv", [1, 1, cin, ex]],
                        [p + ".expand.bn.scale", [ex]],
                        [p + ".expand.bn.bias", [ex]]]
            out += [[p + ".dw.conv", [k, k, 1, ex]], [p + ".dw.bn.scale", [ex]],
                    [p + ".dw.bn.bias", [ex]],
                    [p + ".se.fc1.w", [1, 1, ex, sq]], [p + ".se.fc1.b", [sq]],
                    [p + ".se.fc2.w", [1, 1, sq, ex]], [p + ".se.fc2.b", [ex]],
                    [p + ".project.conv", [1, 1, ex, co]],
                    [p + ".project.bn.scale", [co]],
                    [p + ".project.bn.bias", [co]]]
            nb += 1
    last = 4 * co
    out += [["head.conv", [1, 1, co, last]], ["head.bn.scale", [last]],
            ["head.bn.bias", [last]], ["fc.w", [last, classes]],
            ["fc.b", [classes]]]
    assert nb == 55
    return out


@pytest.mark.parametrize("name,derive,n_leaves,n_params,n_shapes", [
    ("vgg16", vgg16_leaves, 32, 138_357_544, 17),
    ("effnetb7", effnetb7_leaves, 711, 66_347_960, 79),
])
def test_config_holds_published_shapes(name, derive, n_leaves, n_params,
                                       n_shapes):
    cfg = _config(name)
    assert cfg["leaves"] == derive()
    assert len(cfg["leaves"]) == cfg["n_leaves"] == n_leaves
    assert roofline.n_params(cfg) == cfg["n_params"] == n_params
    assert len({tuple(s) for _, s in cfg["leaves"]}) == n_shapes
    assert cfg["dtype"] == "float32" and cfg["reduced"] == []


def test_vgg16_rvl_cdip_head_count():
    """The assumed 1000-class head against RVL-CDIP's 16 classes."""
    assert sum(math.prod(s) for _, s in vgg16_leaves(16)) == 134_326_096


@pytest.mark.parametrize("name,k,want", [
    # FedSGD: 10 updates + the global model read, the new model written
    ("vgg16", 10, (10 + 1 + 1) * 138_357_544 * 4),
    # FedProx publishes the mean: the global model is not read
    ("effnetb7", 10, (10 + 1) * 66_347_960 * 4),
    ("vgg16", 1, 3 * 138_357_544 * 4),
])
def test_least_bytes_per_round_by_hand(name, k, want):
    assert roofline.least_bytes_per_round(_config(name), k) == want


def test_unknown_device_kind_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
