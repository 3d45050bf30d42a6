"""The kernel's exported launch donates its memory planes (PR 42).

`jax.export` keeps the kernel's input/output aliasing inside the
module, but `exp.call` alone runs it in a program whose arguments are
not donated, and XLA then copies every aliased plane around the launch.
The engine wraps the call in `jit_in_place` over `_DONATED_PLANES`, the
memory plane and its shadow, so the launch writes them in place, as the
in-process build does.  A stand-in with the kernel's argument layout
(twelve planes from `_PLANE_ARG0` on, each aliased to an output, the
memory plane the large one) stands for the kernel.
"""

import types

import numpy as np
import pytest

from wasmedge_tpu.batch import jit_in_place
from wasmedge_tpu.batch import pallas_engine as pe

# the memory plane (the fifth) large, the others small
PLANES = tuple((1024 if k == 4 else 8, 4096) for k in range(12))
MEM = [i - pe._PLANE_ARG0 for i in pe._DONATED_PLANES]


def _specs():
    import jax

    i32 = np.int32
    return ([jax.ShapeDtypeStruct((8,), i32)] * pe._PLANE_ARG0
            + [jax.ShapeDtypeStruct(s, i32) for s in PLANES])


def _stand_in():
    """A jitted kernel-like launch: small arguments, then planes it
    writes a row of each in place (the memory planes donated, like
    `_build_kernel`)."""
    import jax

    def launch(*args):
        head, planes = args[:pe._PLANE_ARG0], args[pe._PLANE_ARG0:]
        t = sum(a.sum() for a in head)
        return (t,) + tuple(p.at[1 + k].set(t + k)
                            for k, p in enumerate(planes))

    return jax.jit(launch, donate_argnums=pe._DONATED_PLANES)


def _exported():
    import jax.export as jexport

    exp = jexport.export(_stand_in())(*_specs())
    return jexport.deserialize(bytearray(exp.serialize()))


def _args():
    import jax.numpy as jnp

    rng = np.random.default_rng(42)
    return [jnp.asarray(rng.integers(-9, 9, s.shape, np.int32))
            for s in _specs()]


@pytest.fixture
def cache(tmp_path):
    """-> set(dir): point the persistent compile cache's directory at
    `dir` (None for none), with caching itself off so that nothing is
    written or read back; the previous settings come back after."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def set_dir(d):
        jax.config.update("jax_compilation_cache_dir", d)
        return d

    try:
        yield set_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update("jax_enable_compilation_cache", prev[1])
        compilation_cache.reset_cache()


@pytest.mark.parametrize("persistent", [False, True],
                         ids=["donated", "cpu_cache_carve_out"])
def test_exported_launch_donates_planes(cache, tmp_path, persistent):
    import jax

    cache(str(tmp_path) if persistent else None)
    exp = _exported()
    launch = jit_in_place(exp.call, *pe._DONATED_PLANES)
    n = pe.donated_planes(launch, _specs())
    text = launch.lower(*_specs()).compile().as_text()
    want = jax.jit(exp.call)(*_args())
    args = _args()
    got = launch(*args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    planes = args[pe._PLANE_ARG0:]
    deleted = [k for k, p in enumerate(planes) if p.is_deleted()]
    if persistent:
        # a deserialized CPU executable can lose its aliasing: nothing
        # is donated, and the planes stay the caller's
        assert n == 0 and deleted == []
    else:
        # the memory planes are written in place: neither is copied
        assert n == 2 and deleted == MEM
        assert "input_output_alias" in text
        assert not [x for x in text.splitlines()
                    if " copy(" in x and "s32[1024,4096]" in x]


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_export_cache_returns_the_donating_launch(cache, tmp_path,
                                                  monkeypatch, backend):
    """Both branches of `_with_export_cache`, the fresh export and the
    `kexport/` hit: the launch donates the memory planes on the chip, none
    under the CPU's carve-out."""
    import jax

    d = cache(str(tmp_path))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    built = []

    def build():
        built.append(1)
        return _stand_in()

    eng = types.SimpleNamespace(_interpret=lambda: False,
                                _export_cache_key=lambda: "standin",
                                _arg_specs=_specs)
    want = 2 if backend == "tpu" else 0
    for branch in ("fresh", "hit"):
        launch = pe.PallasUniformEngine._with_export_cache(eng, build)
        assert built == [1], branch       # the hit does not build
        assert (tmp_path / "kexport" / "standin.bin").exists()
        assert pe.donated_planes(launch, _specs()) == want, branch
        args = _args()
        out = launch(*args)
        assert int(out[1][1, 0]) == int(out[0])
        assert [k for k, p in enumerate(args[pe._PLANE_ARG0:])
                if p.is_deleted()] == (MEM if want else []), branch
    assert jax.config.jax_compilation_cache_dir == d
