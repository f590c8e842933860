"""The channel-sharded scalar cell (``hbm3_16ch.stream_i1_4chip``) on the
CPU: a whole run on four forced host devices, with the look for a chip
skipped, comes out correct; with the timed path broken underneath it
comes out not correct, once for each fault the cell can have: a
controller step that returns its state unchanged, an answer altered
where it is produced, and the exchange between the devices left out
(the per-cycle sum over the mesh takes shard 0's part alone)."""
import pytest

from test_bench_correctness import run_on_four_host_devices

CELL = "hbm3_16ch.stream_i1_4chip"
FAULTS = (None, "state_unchanged", "answer_altered", "exchange_left_out")


@pytest.fixture(scope="module")
def correct():
    return run_on_four_host_devices(CELL, FAULTS)


@pytest.mark.parametrize("fault", FAULTS)
def test_channel_sharded_run_is_correct_only_when_the_timed_path_is_sound(
        correct, fault):
    assert correct[str(fault)] is (fault is None)
