"""The unified collective API surface (DESIGN.md §12).

Covers the string-keyed vocabulary tables (one enum shared by the family
functions, the tuning cells, and the comm-ledger labels), the
``collective()``/``Collective``/``finish()`` dispatch layer, and the
deprecation shims for the pre-redesign per-algorithm entry points.
Functional equivalence across all four spellings of the same collective
runs in a forced-multi-device subprocess.
"""
import dataclasses
import warnings

import jax.numpy as jnp
import pytest

from repro.core import collectives as C


# ---------------------------------------------------------------------------
# Vocabulary tables
# ---------------------------------------------------------------------------

def test_vocabulary_tables_consistent():
    assert set(C.ALGORITHMS_BY_KIND) == set(C.KINDS)
    assert set(C.DEFAULT_ALGORITHM) == set(C.KINDS)
    for kind, algs in C.ALGORITHMS_BY_KIND.items():
        assert len(set(algs)) == len(algs), kind
        assert C.DEFAULT_ALGORITHM[kind] in algs, kind


def test_tuning_vocab_is_the_api_vocab():
    from repro.tuning import measure
    assert set(measure.ALL_TO_ALL_ALGORITHMS) <= set(
        C.ALGORITHMS_BY_KIND["all_to_all"])
    assert set(measure.ALLGATHER_ALGORITHMS) <= set(
        C.ALGORITHMS_BY_KIND["allgather"])
    assert set(measure.ALLREDUCE_ALGORITHMS) <= set(
        C.ALGORITHMS_BY_KIND["allreduce"])
    assert set(measure.MIGRATE_ALGORITHMS) <= set(
        C.ALGORITHMS_BY_KIND["cache_migrate"])
    assert set(measure.LOGSUMEXP_ALGORITHMS) <= set(
        C.ALGORITHMS_BY_KIND["combine"])


def test_kind_alias_and_error_paths():
    assert C._norm_kind("logsumexp_combine") == "combine"
    with pytest.raises(ValueError, match="unknown collective kind"):
        C.collective("gathers", jnp.zeros(4), outer="pod")
    with pytest.raises(ValueError, match="unknown algorithm"):
        C.collective("allgather", jnp.zeros(4), outer="pod",
                     algorithm="nope")
    with pytest.raises(NotImplementedError, match="start/finish"):
        C.collective("reduce_scatter", jnp.zeros(4), outer="pod",
                     start=True)
    with pytest.raises(NotImplementedError, match="start/finish"):
        C.collective("cache_migrate", jnp.zeros(4), outer="pod",
                     algorithm="xla", start=True)


def test_collective_dataclass_normalizes_and_freezes():
    c = C.Collective("allgather", outer="pod", local="data")
    assert c.outer == ("pod",) and c.local == ("data",)
    assert C.Collective("combine", outer=("pod",)).local == ()
    with pytest.raises(ValueError, match="unknown collective kind"):
        C.Collective("nope")
    with pytest.raises(dataclasses.FrozenInstanceError):
        c.kind = "allreduce"


# ---------------------------------------------------------------------------
# Deprecated aliases: warn exactly once per process, then forward
# ---------------------------------------------------------------------------

ALIASES = [
    "bruck_allgather", "ring_allgather", "hierarchical_allgather",
    "multilane_allgather", "locality_bruck_allgather",
    "locality_bruck_allgather_start", "locality_bruck_allgather_finish",
    "locality_allreduce", "locality_logsumexp_combine",
    "locality_logsumexp_combine_start", "locality_logsumexp_combine_finish",
]


@pytest.mark.parametrize("name", ALIASES)
def test_deprecated_alias_warns_once(name):
    fn = getattr(C, name)
    C._WARNED.discard(name)     # isolate from other tests in this process
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        for _ in range(2):
            try:
                fn()            # warn fires before arg validation
            except Exception:
                pass
    dep = [r for r in rec if issubclass(r.category, DeprecationWarning)
           and name in str(r.message)]
    assert len(dep) == 1, [str(r.message) for r in rec]
    msg = str(dep[0].message)
    assert "DESIGN.md" in msg and ("collective(" in msg or "finish(" in msg)


# ---------------------------------------------------------------------------
# Functional equivalence of all spellings (subprocess: 4 forced devices)
# ---------------------------------------------------------------------------

API_ROUNDTRIP_CODE = r"""
from repro.launch.mesh import make_mesh
import warnings
import repro  # noqa: F401
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
import repro.core.collectives as C

mesh = make_mesh((2, 2), ("pod", "data"))
x = jnp.arange(4 * 3, dtype=jnp.float32).reshape(4, 3)
run = lambda f, a=x: jax.jit(jax.shard_map(f, mesh=mesh,
    in_specs=P(("pod", "data")), out_specs=P(("pod", "data"))))(a)

# allgather: family fn == collective() == Collective sugar == start/finish
# == deprecated alias, all equal to the lax ground truth
truth = run(lambda s: jax.lax.all_gather(s, ("pod", "data"), tiled=True))
cfgd = C.Collective("allgather", outer="pod", local="data",
                    algorithm="locality_bruck")
variants = {
    "family": lambda s: C.allgather(s, "pod", "data",
                                    algorithm="locality_bruck", tiled=True),
    "collective": lambda s: C.collective("allgather", s, outer="pod",
                                         local="data",
                                         algorithm="locality_bruck",
                                         tiled=True),
    "object": lambda s: cfgd(s, tiled=True),
    "split": lambda s: C.finish(cfgd.start(s, tiled=True)),
}
with warnings.catch_warnings():
    warnings.simplefilter("ignore", DeprecationWarning)
    variants["alias"] = lambda s: C.locality_bruck_allgather(
        s, "pod", "data", tiled=True)
for name, f in variants.items():
    out = run(f)
    assert np.array_equal(np.asarray(out), np.asarray(truth)), name

# allreduce default algorithm == psum ground truth through collective()
tr = run(lambda s: jax.lax.psum(s, ("pod", "data")))
ur = run(lambda s: C.collective("allreduce", s, outer="pod", local="data"))
assert np.allclose(np.asarray(ur), np.asarray(tr))

# all_to_all: locality (default) == flat xla through every spelling
xx = jnp.arange(4 * 4 * 2, dtype=jnp.float32).reshape(16, 2)
ax = run(lambda s: C.all_to_all(s, "pod", "data", algorithm="xla"), xx)
al = run(lambda s: C.collective("all_to_all", s, outer="pod",
                                local="data"), xx)
a2 = C.Collective("all_to_all", outer="pod", local="data")
asplit = run(lambda s: C.finish(a2.start(s)), xx)
assert np.array_equal(np.asarray(al), np.asarray(ax))
assert np.array_equal(np.asarray(asplit), np.asarray(ax))
print("API_ROUNDTRIP_OK")
"""


@pytest.mark.slow
def test_all_spellings_agree(subproc):
    assert "API_ROUNDTRIP_OK" in subproc(API_ROUNDTRIP_CODE, devices=4)
