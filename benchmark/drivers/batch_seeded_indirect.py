"""drivers/batch_seeded_indirect.py: `drivers/batch_seeded.py` for a guest
whose kernel counts its `br_table` and `call_indirect`, and whose lanes
all run one argument: CoreMark, whose run is defined by its seeds.

The seeded driver names the engine counters it sums job by job in a
constant, `ENGINE_COUNTERS`, which lacks `indirect_ops`
(`eng.pallas.indirect_ops`: the `br_table` and `call_indirect` the
kernels ran, which only a kernel whose image holds one counts), and no
file here is edited.  So this file loads a copy of that driver of its
own, appends the one name to the copy's constant, and gives the copy a
checker of its own:

- CoreMark's instruction count is no product of the guest's sizes (the
  seeded checker's `expected.retired_formula`): every lane is held to
  `expected.retired_per_lane`, and a rehearsal to its own
  `retired_per_lane` (the workload's `rehearse`), both the scalar
  engine's;
- at the cell's parameters the reference must give CoreMark's published
  CRCs (`expected.crclist`, `crcmatrix`, `crcstate`) before any lane is
  held to it, so that a lane is held to CoreMark and not to the
  reference alone;
- a job whose block split counts every lane as failed: lanes that run
  one argument never diverge, so a split is a fault.

The uniform lane argument is `drivers/batch.py`'s `{kind: uniform}`,
which the seeded driver hands on.  Everything else (the guest's builder
looked up before anything touches the device, so that a program that
lacks it ends at once; the checker's 64 bits, trap and count a lane; the
window's counters of the traced slice) is the seeded driver's own.
"""

import numpy as np

import harness

seeded = harness.load_module("drivers", "batch_seeded")
seeded.ENGINE_COUNTERS = seeded.ENGINE_COUNTERS + ("indirect_ops",)


class Checker(seeded.Checker):
    """Every lane's 64 bits against the plain reference, asked once for
    all lanes, and every lane's retired count against the scalar
    engine's."""

    engine = None       # the engine `build_engine` below made

    def __init__(self, run, func, args):
        sizes = run.config["guest"].get("args", {})
        self.expect = np.asarray(run.reference().reference_lanes(
            func, args, **sizes)).astype(np.uint64)
        expected = run.workload["expected"]
        if run.rehearse:
            self.retired = run.workload["rehearse"]["retired_per_lane"]
            return
        self.retired = expected["retired_per_lane"]
        published = (int(expected["crclist"], 16)
                     | int(expected["crcmatrix"], 16) << 16
                     | int(expected["crcstate"], 16) << 32)
        if np.any((self.expect >> np.uint64(16)) != np.uint64(published)):
            raise RuntimeError(
                "the reference misses CoreMark's published CRCs: "
                f"{hex(int(self.expect[0]))}")

    def bad_lanes(self, res):
        bad, retired, lane_steps = super().bad_lanes(res)
        if self.engine.pallas.splits:
            bad = len(self.expect)
        return bad, retired, lane_steps


def build_engine(config, builder):
    Checker.engine = _build_engine(config, builder)
    return Checker.engine


_build_engine = seeded.build_engine
seeded.build_engine = build_engine
seeded.Checker = Checker
run = seeded.run
