"""TPU machine model: compute roofline + ICI/DCN communication costs.

Reference: ``src/runtime/machine_model.cc`` (``SimpleMachineModel`` /
``EnhancedMachineModel`` describing PCIe/NVLink/IB bandwidths).  The TPU
analogue describes per-chip peak FLOPs + HBM bandwidth and the ICI torus
links within a slice (DCN across slices).  Numbers are calibratable: the
microbenchmark harness (``measure.py``) can overwrite the analytical guesses
with measured values — the ``[B]`` "recalibrate the simulator" requirement.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass
class TPUSpec:
    name: str
    peak_flops_bf16: float      # FLOP/s per chip
    peak_flops_f32: float
    hbm_bandwidth: float        # bytes/s
    ici_bandwidth: float        # bytes/s per link direction
    ici_latency: float          # seconds per hop
    dcn_bandwidth: float        # bytes/s per host
    dcn_latency: float
    kernel_overhead: float = 2e-6   # per-op overhead (legacy per-op roofline)
    hbm_capacity: float = 16e9      # bytes per chip (memory-aware search)
    # fused-program constants — spec-sheet defaults, overridden by measured
    # values via ``MachineModel.with_calibration`` (search/measure.py writes
    # them; VERDICT r3 #4 "constants no longer literals"):
    mxu_efficiency: float = 0.5     # achievable fraction of peak on real GEMMs
    vmem_resident_bytes: float = 6.4e7  # weights below this stay VMEM-resident
    step_overhead: float = 3e-6     # per compiled-step dispatch/loop overhead
    train_step_factor: float = 3.0  # whole train step time / forward time
    overlap: float = 0.3            # comm fraction hidden behind compute
    # host-tier KV swap (serve/kv_paged.py): device<->host-DRAM link the
    # spill/restore transfers ride (PCIe-class; TPU hosts see ~8-32 GB/s
    # effective).  Defaults here so every spec entry prices swaps without
    # per-generation numbers; calibratable like every constant.
    host_bandwidth: float = 12.5e9  # bytes/s, device<->host
    host_latency: float = 20e-6     # per-transfer setup
    # speculative serving (serve/spec_infer.py): the draft-token acceptance
    # rate at which one speculative macro-step (depth draft levels + one
    # tree-verify pass) costs the same PER TOKEN as incremental decoding —
    # macro_cost = tpot * (1 + break_even * depth) by definition, so the
    # serve search prices a spec plan as tpot * (1 + be*d) / (1 + a*d) for
    # live acceptance a (search/serve_search.py).  NOT MEASURED on this
    # installation (ROADMAP C6): 0.439 is a figure from before the chip,
    # at depth 5; calibratable like every constant here (with_calibration
    # field + CalibrationStore time-like scaling — a machine whose verify
    # step is relatively slower than modeled raises the break-even).
    spec_break_even_acceptance: float = 0.439


TPU_SPECS: Dict[str, TPUSpec] = {
    # public spec-sheet numbers (approximate; calibrate on real hardware)
    "v5e": TPUSpec(
        name="v5e",
        peak_flops_bf16=197e12,
        peak_flops_f32=98.5e12,
        hbm_bandwidth=819e9,
        ici_bandwidth=0.2e12,      # 1.6 Tbps total / 8 ≈ per-direction-link bytes
        ici_latency=1e-6,
        dcn_bandwidth=25e9,
        dcn_latency=10e-6,
        hbm_capacity=16e9,
    ),
    "v5p": TPUSpec(
        name="v5p",
        peak_flops_bf16=459e12,
        peak_flops_f32=229.5e12,
        hbm_bandwidth=2765e9,
        ici_bandwidth=0.6e12,
        ici_latency=1e-6,
        dcn_bandwidth=25e9,
        dcn_latency=10e-6,
        hbm_capacity=95e9,
    ),
    # virtual CPU mesh for hermetic tests: only relative costs matter
    "cpu": TPUSpec(
        name="cpu",
        peak_flops_bf16=200e9,
        peak_flops_f32=100e9,
        hbm_bandwidth=20e9,
        ici_bandwidth=5e9,
        ici_latency=5e-6,
        dcn_bandwidth=1e9,
        dcn_latency=50e-6,
        hbm_capacity=8e9,   # virtual-device test budget
    ),
}


# ``device.device_kind`` as JAX reports it -> TPU_SPECS key.  A device that
# is not here is an error, never priced as some other chip.
DEVICE_KIND_SPECS: Dict[str, str] = {
    "TPU v5 lite": "v5e",
    "TPU v5": "v5p",
    "cpu": "cpu",
}


@dataclasses.dataclass
class MachineModel:
    """Cost oracle for one mesh: compute roofline + collective time."""

    spec: TPUSpec
    # mesh axes laid out over ICI by default; axes listed here ride DCN
    dcn_axes: frozenset = frozenset()

    @staticmethod
    def for_mesh(mesh, spec_name: Optional[str] = None,
                 dcn_axes=()) -> "MachineModel":
        if spec_name is None:
            kind = mesh.devices.flat[0].device_kind
            if kind not in DEVICE_KIND_SPECS:
                raise ValueError(
                    f"no machine spec for device_kind {kind!r} (known: "
                    f"{sorted(DEVICE_KIND_SPECS)}); add it to TPU_SPECS / "
                    "DEVICE_KIND_SPECS or pass spec_name explicitly")
            spec_name = DEVICE_KIND_SPECS[kind]
        return MachineModel(TPU_SPECS[spec_name], frozenset(dcn_axes))

    def with_calibration(self, path: str) -> "MachineModel":
        """Return a copy whose fused-program constants come from a measured
        calibration JSON (``measure.calibrate_machine_constants`` writes it).
        Missing file or keys leave the spec-sheet defaults in place."""
        import json
        import os

        if not os.path.exists(path):
            return self
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            return self
        fields = ("mxu_efficiency", "vmem_resident_bytes", "step_overhead",
                  "train_step_factor", "overlap",
                  "spec_break_even_acceptance",
                  "host_bandwidth", "host_latency")
        spec = dataclasses.replace(
            self.spec,
            **{k: float(doc[k]) for k in fields if k in doc},
        )
        return MachineModel(spec, self.dcn_axes)

    # spec constants a CalibrationStore may scale, by dimensional sense:
    # a measured/predicted TIME ratio > 1 means the machine is slower than
    # modeled -> time-like constants multiply by the scale, rate-like
    # constants divide by it
    _TIME_CONSTANTS = frozenset({
        "step_overhead", "kernel_overhead", "ici_latency", "dcn_latency",
        "host_latency", "train_step_factor",
        # relatively slower verify/draft steps raise the acceptance needed
        # to break even — time-like (multiplies by the measured/predicted
        # ratio), so a CalibrationStore component named after it scales
        # the spec pricing like any machine constant
        "spec_break_even_acceptance",
    })
    _RATE_CONSTANTS = frozenset({
        "hbm_bandwidth", "ici_bandwidth", "dcn_bandwidth",
        "host_bandwidth", "peak_flops_bf16", "peak_flops_f32",
        "mxu_efficiency",
    })

    def with_store(self, store) -> "MachineModel":
        """Return a copy whose spec constants are corrected by a persisted
        :class:`~flexflow_tpu.obs.calibration.CalibrationStore`.

        Only store components NAMED after a spec constant apply here
        (``step_overhead``, ``mxu_efficiency``, ...); field-level
        components (``tpot_ms``, ``transfer_ms``, ...) are consumed by
        ``search_serve_plan`` at the prediction layer instead.  Scales
        below the store's min-sample gate are ignored (``scale_for``
        returns 1.0), and an empty/None store returns ``self`` unchanged —
        so this COMPOSES with :meth:`with_calibration`: measured constants
        load first, the store's cross-run drift corrections stack
        multiplicatively on top, and neither clobbers the other
        (pinned by tests/test_calibration_loop.py).
        """
        if store is None:
            return self
        updates = {}
        for name in self._TIME_CONSTANTS | self._RATE_CONSTANTS:
            s = store.scale_for(name)
            if s == 1.0:
                continue
            v = getattr(self.spec, name)
            updates[name] = v * s if name in self._TIME_CONSTANTS else v / s
        if not updates:
            return self
        return MachineModel(dataclasses.replace(self.spec, **updates),
                            self.dcn_axes)

    # ---- compute ------------------------------------------------------
    def compute_time(self, flops: float, bytes_accessed: float,
                     dtype_bits: int = 32) -> float:
        peak = (
            self.spec.peak_flops_bf16
            if dtype_bits <= 16
            else self.spec.peak_flops_f32
        )
        return max(flops / peak, bytes_accessed / self.spec.hbm_bandwidth) + (
            self.spec.kernel_overhead
        )

    # ---- communication ------------------------------------------------
    def transfer_time(self, nbytes: float, axes=()) -> float:
        """Point-to-point device-to-device transfer time (the inter-stage
        activation hop of pipeline-parallel serving: collective-permute /
        ICI copy between adjacent stage slices).  ``axes``: mesh axes the
        hop crosses — listed in ``dcn_axes`` means the slower DCN path."""
        if nbytes <= 0:
            return 0.0
        on_dcn = any(a in self.dcn_axes for a in axes)
        bw = self.spec.dcn_bandwidth if on_dcn else self.spec.ici_bandwidth
        lat = self.spec.dcn_latency if on_dcn else self.spec.ici_latency
        return nbytes / bw + lat

    def swap_time(self, nbytes: float) -> float:
        """Device<->host-DRAM transfer time for one KV spill or restore
        (serve/kv_paged.py HostPageTier).  The planner compares this
        against recompute-prefill cost (``serve_search.price_kv_swap``)
        to decide, per workload, whether a host tier pays off."""
        if nbytes <= 0:
            return 0.0
        return nbytes / self.spec.host_bandwidth + self.spec.host_latency

    def collective_time(self, comm_bytes_per_device: float, axes, mesh) -> float:
        """Ring-model time for a collective moving ``comm_bytes_per_device``
        over the given mesh axes (the per-op ``comm_bytes`` hook supplies the
        bytes; (deg-1)/deg factors are already baked in there)."""
        if comm_bytes_per_device <= 0:
            return 0.0
        deg = 1
        for a in axes:
            deg *= mesh.shape[a]
        if deg <= 1:
            return 0.0
        on_dcn = any(a in self.dcn_axes for a in axes)
        bw = self.spec.dcn_bandwidth if on_dcn else self.spec.ici_bandwidth
        lat = self.spec.dcn_latency if on_dcn else self.spec.ici_latency
        return comm_bytes_per_device / bw + (deg - 1) * lat
