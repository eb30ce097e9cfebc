"""Accelerator type constants for scheduling constraints.

Reference capability: python/ray/util/accelerators/accelerators.py —
string constants users pass as ``accelerator_type=`` so tasks land on
nodes with that hardware. The reference ships GPU types only (**no
TPU** — SURVEY.md §2.4 flags this); the TPU generations are the
first-class citizens here, with the reference's GPU names kept for
migration compatibility.

The constant doubles as a custom-resource name: the autoscaler's TPU
pod provider advertises ``accelerator_type:<TYPE>`` on matching nodes,
and ``@remote(resources={accelerator_resource(TPU_V5E): 1})`` pins
placement.
"""

# TPU generations (the native citizens)
TPU_V2 = "TPU-V2"
TPU_V3 = "TPU-V3"
TPU_V4 = "TPU-V4"
TPU_V5E = "TPU-V5E"      # a.k.a. v5 lite
TPU_V5P = "TPU-V5P"
TPU_V6E = "TPU-V6E"      # Trillium

# reference GPU names kept for migration compatibility
NVIDIA_TESLA_V100 = "V100"
NVIDIA_TESLA_P100 = "P100"
NVIDIA_TESLA_T4 = "T4"
NVIDIA_TESLA_P4 = "P4"
NVIDIA_TESLA_K80 = "K80"
NVIDIA_TESLA_A10G = "A10G"
NVIDIA_TESLA_A100 = "A100"
NVIDIA_H100 = "H100"
AMD_INSTINCT_MI100 = "AMD-Instinct-MI100"
INTEL_MAX_1550 = "Intel-GPU-Max-1550"

_ALL = {v for k, v in list(globals().items())
        if k.isupper() and isinstance(v, str)}


def accelerator_resource(accelerator_type: str) -> str:
    """Custom-resource name a node advertises for this accelerator."""
    return f"accelerator_type:{accelerator_type}"


def is_known_accelerator(accelerator_type: str) -> bool:
    return accelerator_type in _ALL


_TPU_KINDS = (("v5 lite", TPU_V5E), ("v5e", TPU_V5E), ("v5p", TPU_V5P),
              ("v6", TPU_V6E), ("v4", TPU_V4), ("v3", TPU_V3),
              ("v2", TPU_V2))


def tpu_type_of(device_kind: str) -> str:
    """``jax.Device.device_kind`` → accelerator constant ("" when the
    kind names no TPU generation, e.g. "cpu")."""
    kind = device_kind.lower()
    for key, const in _TPU_KINDS:
        if key in kind:
            return const
    return ""


def detect_tpu_type() -> str:
    """TPU generation of the locally visible chip ("" on CPU-only
    hosts).  A backend that fails to initialize raises — a node must
    not advertise "no TPU" because the TPU runtime is broken."""
    import jax
    return tpu_type_of(jax.devices()[0].device_kind)


# Published per-chip peaks: (bf16 FLOP/s, HBM bytes/s).  Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).  The
# benchmark divides by its own copy (chipbench/peaks.json); the scripts
# that read this one went with PR 47 (ROADMAP.md C8).
CHIP_PEAKS = {
    TPU_V5E: (197e12, 819e9),
}


def chip_peaks(device_kind: str) -> tuple[float, float]:
    """(peak bf16 FLOP/s, peak HBM bytes/s) for a jax ``device_kind``.
    An unknown device is an error, never a default: a utilization
    computed against a guessed peak is not a measurement."""
    tpu_type = tpu_type_of(device_kind)
    if tpu_type not in CHIP_PEAKS:
        raise ValueError(
            f"no published peak rates for device_kind {device_kind!r}; "
            f"known: {sorted(CHIP_PEAKS)} — add the chip (with its "
            "source) to ray_tpu.util.accelerators.CHIP_PEAKS")
    return CHIP_PEAKS[tpu_type]
