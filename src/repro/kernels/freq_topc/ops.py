"""jit'd public wrapper and the ONE dispatch site for FrequentOnes top-C.

Which implementation runs is not decided here: ``kernel`` carries the
choice made where the request's params resolved (``SearchParams.resolve``
puts "freq_topc" in ``SearchParams.kernels`` on a TPU while the padded
candidate width fits ``MAX_WIDTH``). The Pallas kernel and the jnp oracle
return identical output."""
from repro.kernels.freq_topc.freq_topc import freq_topc
from repro.kernels.freq_topc.ref import freq_topc_ref


def frequent_topc(cands, *, C: int, kernel: bool = False, tq: int = 8):
    if kernel:
        return freq_topc(cands, C=C, tq=tq)
    return freq_topc_ref(cands, C=C)


# ------------------------------------------------------- static contracts --
from repro.analysis import contracts as _C


def _freq_fixture():
    from repro.analysis import fixtures as _FX
    return _FX.freq_topc_fixture()


def _freq_dense_control():
    from repro.analysis import fixtures as _FX
    return _FX.freq_topc_fixture(dense=True)


_C.register(_C.Contract(
    id="kernels.freq_topc.no_dense_histogram",
    site="repro.kernels.freq_topc.ops.frequent_topc",
    description="FrequentOnes top-C counts candidates by sort + run-length, "
                "never via a [Q, L] histogram (the control builds one)",
    fixture=_freq_fixture,
    checks=[_C.forbid_dims("Q", "L"), _C.require_dims("Q", "C")],
    control=_freq_dense_control,
))
