"""``fold_device_ms_per_update.backlog``: the device time of the fold's
programs per update, read from a hand-built window to the nanosecond."""
import pytest

from chipbench import trace as T
from chipbench.plan import REPO, load_reader

DEV = "/device:TPU:0"


def _reader():
    return load_reader(REPO / "chipbench" / "metrics"
                       / "fold_device_ms_per_update.backlog.py")


def _window(programs):
    """One round of K=2: the first fold, a fold and the finish, launched on
    one chip."""
    spans = [T.Event("round", 0, 10_000, (("round", 0),))]
    return T.window(T.Trace(spans, programs, {DEV: []}), 1000, 2, 1e12)


@pytest.mark.parametrize("chips", [1, 2])
def test_reads_first_fold_and_fold_into_per_update(chips):
    e = T.Event
    launches = [e("jit_first_fold(11)", 100, 1_100),
                e("jit_fold_into(12)", 1_200, 3_700),
                e("jit_finished_model(13)", 3_800, 4_800),
                e("jit_pair_fuse", 5_000, 9_000)]
    w = _window({f"/device:TPU:{i}": launches for i in range(chips)})
    assert _reader()(w) == pytest.approx((1_000 + 2_500) / 1e6 / 2)


def test_nothing_to_read_without_the_fold_programs():
    w = _window({DEV: [T.Event("jit_finished_model", 3_800, 4_800)]})
    assert _reader()(w) is None
    assert _reader()(_window({})) is None
