"""drivers/batch_seeded_simd.py: `drivers/batch_seeded.py` for a guest
whose kernel counts its v128 instructions.

The seeded driver names the engine counters it sums job by job in a
constant, `ENGINE_COUNTERS`, which lacks `simd_ops` (`eng.pallas.simd_ops`:
the instructions of a v128 class the kernels ran, which only a kernel
whose image has v128 counts), and no file here is edited.  So this file
loads a copy of that driver of its own, appends the one name to the
copy's constant and hands on its `run`: everything else (the guest's
builder looked up before anything touches the device, so that a program
that lacks it ends at once; the distinct seeds; the checker that holds
every lane's 64 bits, its retired count and `trap == -1`; the window's
counters of the traced slice) is the seeded driver's own.
"""

import harness

seeded = harness.load_module("drivers", "batch_seeded")
seeded.ENGINE_COUNTERS = seeded.ENGINE_COUNTERS + ("simd_ops",)

run = seeded.run
