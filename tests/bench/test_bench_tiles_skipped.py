"""``tiles_skipped_pct``: the share of the fused kernels' grid steps with
no tile work, counted from a round record, against a hand count and a
step-by-step count; silent where the program does not say it skips."""
import inspect

import numpy as np
import pytest

from bench import harness
from repro import obs
from repro.core import CounterfactualEngine
from repro.core.segments import REDUCE_BLOCKS

ROOT = harness.os.path.dirname(harness.os.path.dirname(
    harness.os.path.abspath(harness.__file__)))
READER = harness.load_reader(ROOT, "tiles_skipped_pct.batch")

# N = 2048: 32 blocks of 64 rows, one 64-row tile each. Lane 0 runs two
# rounds (caps at 640, then none to the end), lane 1 one (caps at 1000).
NUM_ROUNDS = np.array([2, 1])
BOUNDARIES = np.array([[0, 640, 2048, 0], [0, 1000, 0, 0]])
# round 0, both lanes: rate pass 32 + 32 tiles, block pass 10 + 16;
# round 1, lane 0: 22 tiles in each pass. 2 rounds x 2 passes x 32 tiles
# x 2 lanes = 256 steps.
HAND = 100.0 * (1.0 - (64 + 26 + 44) / 256)


def _sweep_span(**attrs):
    return obs.Record(1, None, "executor.sweep", 0.0, 0.001,
                      dict(placement="batched", lanes=2, events=2048,
                           layout="once", **attrs))


def _run(shards=None):
    run = {"obs": {"n_events": 2048,
                   "round_record": {"num_rounds": NUM_ROUNDS,
                                    "boundaries": BOUNDARIES}},
           "trace": None}
    if shards is not None:
        run["obs"]["shards"] = shards
    return run


@pytest.fixture
def recorded(monkeypatch):
    def use(records):
        monkeypatch.setattr(obs, "records", lambda: list(records))
        monkeypatch.setattr(obs, "dropped", lambda: 0)
    return use


@pytest.mark.parametrize("shards", [None, 1, 2, 4])
def test_a_hand_made_round_record(recorded, shards):
    """Shards holding whole blocks have the one-chip tiles between them,
    so the share summed over chips is the one-chip share."""
    recorded([_sweep_span(tile_skip="window")])
    assert READER.read(_run(shards)) == pytest.approx(HAND)


@pytest.mark.parametrize("records", [[], [_sweep_span()]],
                         ids=["no_sweep", "no_tile_skip"])
def test_silent_where_the_program_does_not_skip(recorded, records):
    recorded(records)
    assert READER.read(_run()) is None


def _step_by_step(num_rounds, boundaries, n_events, shards, block_t):
    """The same share, one (round, chip, pass, tile, lane) step at a time."""
    block_size = -(-n_events // REDUCE_BLOCKS)
    t = min(block_t, -(-block_size // 8) * 8)
    per_block = -(-block_size // t)
    local = n_events // shards
    live = steps = 0
    for j in range(max(num_rounds)):
        for k in range(shards):
            rows = range(k * local, (k + 1) * local)
            blocks = range(rows[0] // block_size,
                           (rows[-1] // block_size) + 1)
            for tail in (None, 1):
                for g in blocks:
                    for i in range(per_block):
                        start = g * block_size + i * t
                        end = min(start + t, (g + 1) * block_size)
                        for s in range(len(num_rounds)):
                            steps += 1
                            if num_rounds[s] <= j:
                                continue
                            hi = n_events if tail is None \
                                else boundaries[s][j + 1]
                            lo = max(boundaries[s][j], rows[0])
                            hi = min(hi, rows[-1] + 1)
                            live += max(start, lo) < min(end, hi)
    return 100.0 * (1 - live / steps)


@pytest.mark.parametrize("n_events,shards,block_t", [
    (2000, 1, 256),     # ragged: the last block ends past N
    (20480, 1, 256),    # 640-row blocks, three tiles each
    (20480, 4, 256),
    (4096, 2, 64),      # several tiles a block, two chips
])
def test_matches_a_step_by_step_count(monkeypatch, n_events, shards,
                                      block_t):
    monkeypatch.setattr(READER, "BLOCK_T", block_t)
    rng = np.random.default_rng(n_events + shards)
    lanes, c = 5, 6
    num_rounds = rng.integers(1, c + 2, lanes)
    boundaries = np.zeros((lanes, c + 2), np.int64)
    for s in range(lanes):
        cuts = np.sort(rng.integers(0, n_events, num_rounds[s] - 1))
        boundaries[s, 1:num_rounds[s] + 1] = np.append(cuts, n_events)
    got = READER.skipped_pct(num_rounds, boundaries, n_events, shards)
    want = _step_by_step(num_rounds.tolist(), boundaries.tolist(),
                         n_events, shards, block_t)
    assert got == pytest.approx(want)


def test_the_geometry_is_the_programs():
    assert READER.REDUCE_BLOCKS == REDUCE_BLOCKS
    block_t = inspect.signature(CounterfactualEngine.sweep).parameters[
        "block_t"].default
    assert READER.BLOCK_T == block_t


def test_the_one_chip_sweep_cells_list_it():
    for cell, name in (("paper71.grid32", "tiles_skipped_pct.batch"),
                       ("paper71.single", "tiles_skipped_pct.single")):
        names = {m["name"] for m in harness.cell_metrics(
            harness.load_spec(ROOT), cell, "per_layer")}
        assert name in names, cell
