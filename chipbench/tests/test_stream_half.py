"""``vgg16.stream_half``: the VGG16 configuration under the open loop at
half the knee, resolved from its files alone."""
import json

from chipbench import plan
from chipbench.plan import REPO


def _json(kind, name):
    return json.loads((REPO / "chipbench" / kind / f"{name}.json").read_text())


def test_stream_half_resolves_to_its_files():
    cell = plan.load_cell("vgg16.stream_half")
    assert cell.chips == 1 and cell.config == _json("configs", "vgg16")
    assert cell.mix == dict(_json("mixes", "poisson_19.2"),
                            rate_updates_per_s=12,
                            about=cell.mix["about"])
    names = {m.name for m in cell.end_to_end + cell.per_layer}
    assert {"setup_s", "round_latency_p50_ms", "device_ms_per_update.stream",
            "fold_host_ms_per_update.stream",
            "finish_host_ms_per_round.stream"} <= names
    assert not {"updates_per_s", "drain_host_ms_per_update.stream"} & names
