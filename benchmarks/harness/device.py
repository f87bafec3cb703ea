"""The chips a run measures on: the check that they are there, and the
memory reading."""

from __future__ import annotations


class NoAccelerator(RuntimeError):
    pass


def require_devices(chips: int, allow_cpu: bool = False) -> list:
    """The first ``chips`` devices, or :class:`NoAccelerator`. A measurement
    never falls back to the CPU; ``allow_cpu`` is for the tests, which steer
    it from the test and produce no device number."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu" and not allow_cpu:
        raise NoAccelerator("JAX found no accelerator (platform cpu)")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips and JAX "
                            f"found {len(devices)}")
    return list(devices[:chips])


def describe() -> dict:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def peak_bytes(devices, allow_cpu: bool = False) -> int:
    """Peak bytes of the fullest device: ``peak_bytes_in_use`` plus
    ``peak_bytes_reserved``. On the TPU a loaded program's temporaries (the
    activations of a whole step) live in the runtime's reserved region,
    which ``peak_bytes_in_use`` leaves out: alone it reads the parameters
    and a batch, a twentieth of what decides whether a batch fits."""
    stats = [d.memory_stats() or {} for d in devices]
    print(f"[bench] memory_stats {stats}", flush=True)
    if all("peak_bytes_in_use" in s for s in stats):
        return int(max(s["peak_bytes_in_use"]
                       + s.get("peak_bytes_reserved", 0) for s in stats))
    if not allow_cpu:
        raise RuntimeError("the backend reports no peak_bytes_in_use")
    import resource  # tests on the CPU backend: host RSS stands in
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
