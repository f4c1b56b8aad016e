# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
#
# Shared TPU names for every kernel in this package: the memory spaces, the
# VMEM limit a kernel may claim, and the per-core VMEM size by device kind.
from jax.experimental.pallas import tpu as _pltpu

ANY = _pltpu.MemorySpace.ANY    # compiler-chosen (HBM for big tables)
VMEM = _pltpu.VMEM              # fast on-chip vector memory (scratch ctor)
SMEM = _pltpu.SMEM              # scalar memory (scratch ctor)

#: Per-TensorCore VMEM bytes, keyed by ``jax.Device.device_kind`` (Google
#: Cloud TPU system-architecture pages; the same figures as jax's own
#: ``pallas.tpu.get_tpu_info`` table).
VMEM_BYTES = {
    "TPU v4": 16 << 20,
    "TPU v5 lite": 128 << 20,
    "TPU v5e": 128 << 20,
    "TPU v5": 64 << 20,
    "TPU v5p": 64 << 20,
    "TPU v6 lite": 128 << 20,
    "TPU v6e": 128 << 20,
}


def vmem_bytes(device_kind: str) -> int:
    """Per-core VMEM of ``device_kind``. A kind missing from the table is an
    error, never a guessed size: a wrong budget would only show up as a
    compile refusal or a silently shrunk tile."""
    try:
        return VMEM_BYTES[device_kind]
    except KeyError:
        raise ValueError(
            f"no VMEM size recorded for device kind {device_kind!r}; add it "
            "to repro.kernels.VMEM_BYTES") from None


def vmem_limit_bytes(n: int):
    """The ``compiler_params`` value capping a kernel's VMEM allocation at
    ``n`` bytes — a kernel passes its accounted budget through here so an
    accounting bug surfaces as a compile error, not an OOM."""
    return _pltpu.CompilerParams(vmem_limit_bytes=int(n))


__all__ = ["ANY", "VMEM", "SMEM", "VMEM_BYTES", "vmem_bytes",
           "vmem_limit_bytes"]
