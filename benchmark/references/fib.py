"""The plain reference of the fib guest: the same function in plain
Python, independent of every engine.  Results are the raw 64-bit cells a
wasm i32 result occupies."""


def fib(n):
    a, b = 0, 1
    for _ in range(max(n, 0)):
        a, b = b, a + b
    return a if n >= 0 else n   # the guest returns n itself below 2


def reference(func, args):
    if func != "fib":
        raise KeyError(func)
    return [fib(int(args[0])) & 0xFFFFFFFF]
