"""The plain reference of the memory guest (`build_memory_workload`,
export `mem_checksum`): the guest's text followed word for word in numpy
integers, independent of every engine.  Pass p counts from `passes` down
to 1; it stores word i = i * 0x9E3779B1 xor (p - 1) for i in 0..n-1 and
then folds the n words it reads back into the running accumulator, by
`i32.add` (modulo 2**32) in the configuration's guest, the 64-pass build
`build_memory_batch`, and by `i32.xor` in `build_memory_workload`'s
default.  Under xor an even pass count cancels to 0 whatever the memory
holds; under add the answer moves with every word of every pass.
Results are the raw 64-bit cells a wasm i32 result occupies."""

import numpy as np

PASSES = 64             # build_memory_batch
FOLD = "add"            # build_memory_batch
PAGE_WORDS = 16384      # the guest declares one 64 KiB page


def mem_checksum(n, passes=PASSES, fold=FOLD):
    if not 0 <= n <= PAGE_WORDS:
        raise ValueError(f"{n} words do not fit the guest's one page")
    i = np.arange(n, dtype=np.uint32)
    memory = np.zeros(PAGE_WORDS, np.uint32)
    acc = 0
    for p in range(passes, 0, -1):
        memory[:n] = (i * np.uint32(0x9E3779B1)) ^ np.uint32(p - 1)
        if fold == "add":
            acc = (acc + int(memory[:n].sum(dtype=np.uint64))) % 2**32
        elif fold == "xor":
            acc ^= int(np.bitwise_xor.reduce(memory[:n],
                                             initial=np.uint32(0)))
        else:
            raise ValueError(f"unknown fold {fold!r}")
    return acc


def reference(func, args):
    if func != "mem_checksum":
        raise KeyError(func)
    return [mem_checksum(int(args[0])) & 0xFFFFFFFF]
