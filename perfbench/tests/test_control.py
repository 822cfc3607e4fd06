"""The controls come out as not correct.

A control is the program with its own lower-precision path switched on
(``control`` in the configuration file): fp8 matmuls for the bfloat16
trainer, int8 weights and int8 keys and values for the bfloat16 server.
On the chip they were read at the cells' own sizes (PERF.md gives the
readings the limits were set from); here they run at a size a test can
hold. The benchmark's own runs never run them.
"""

import importlib
import os

from perfbench.device import require_chips
from perfbench.manifest import Manifest

REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "rehearsal")


def readings(cell_name, variant, seed, seconds):
    cell = Manifest(REHEARSAL).cell(cell_name)
    device = require_chips(cell["chips"], allow_cpu=True)
    family = importlib.import_module(
        f"perfbench.families.{cell['config']['family']}")
    system = family.build(cell["config"], device, seed, variant)
    system.warm_up(cell["traffic"], seconds)
    record = system.run_window(cell["traffic"], seconds)
    system.release()
    return {c["name"]: (c["value"], c["limit"])
            for c in system.check(record)["comparisons"]}


def test_fp8_trainer_fails_the_gradient_comparison():
    for seed in (1, 2, 2**31 + 3):
        sound = readings("bert-base-sst2.b256", "program", seed, 0.3)
        control = readings("bert-base-sst2.b256", "control", seed, 0.3)
        assert all(v <= lim for v, lim in sound.values()), sound
        value, limit = control["first_grad_median_leaf_error"]
        assert value > limit, control
        assert value > 3 * sound["first_grad_median_leaf_error"][0]
        # The loss at seeded weights hardly moves: it is there to catch a
        # part of the batch left out, not a lower precision.
        assert control["loss_step1_gap"][0] <= control["loss_step1_gap"][1]


def test_int8_server_fails_the_mean_margin():
    for seed in (1, 2, 2**31 + 3):
        sound = readings("small-decoder.small-chat", "program", seed, 4.0)
        control = readings("small-decoder.small-chat", "control", seed, 4.0)
        assert all(v <= lim for v, lim in sound.values()), sound
        value, limit = control["mean_logit_margin"]
        assert value > limit, control
        # The widest gap swings by its nature and is held to whole
        # logits' worth of fault, not to a precision.
        assert control["wrong_token_count"] == (0, 0)
