"""Device identity ("Place") for the TPU-native framework.

Reference parity: `paddle/fluid/platform/place.h:26-98` models CPUPlace /
CUDAPlace / CUDAPinnedPlace as a boost::variant. Here a Place maps onto a JAX
device; `TPUPlace` is first-class (the north star adds it next to CPUPlace and
CUDAPlace). `CUDAPlace` is kept as an API alias that resolves to the best
accelerator present so reference scripts run unmodified.
"""
from __future__ import annotations

import functools


class Place:
    """Base device identity. Resolves lazily to a concrete `jax.Device`."""

    _kind = "undefined"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    # -- identity ---------------------------------------------------------
    def get_device_id(self) -> int:
        return self._device_id

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self._kind == other._kind
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self._kind, self._device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self._device_id)

    # -- resolution -------------------------------------------------------
    def jax_device(self):
        """Return the concrete jax.Device this place denotes."""
        devs = _devices_of_kind(self._kind)
        if not 0 <= self._device_id < len(devs):
            raise ValueError(
                "%r names device %d but the %s backend has %d device(s)"
                % (self, self._device_id, devs[0].platform, len(devs)))
        return devs[self._device_id]

    def is_cpu_place(self):
        return self._kind == "cpu"

    def is_gpu_place(self):
        return self._kind == "accel"

    def is_tpu_place(self):
        return self._kind == "accel"


@functools.lru_cache(maxsize=None)
def _devices_of_kind(kind: str):
    import jax

    if kind == "cpu":
        try:
            return tuple(jax.devices("cpu"))
        except RuntimeError:
            return tuple(jax.devices())
    # "accel": the devices of the default backend — the chips under
    # libtpu, the virtual CPU devices of the test mesh otherwise
    return tuple(jax.devices())


class CPUPlace(Place):
    _kind = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    """First-class TPU device identity (north star: paddle.TPUPlace):
    `TPUPlace(i)` is chip `i`, and an index that does not exist raises.

    Test mode: where the default backend is the CPU (the tests' virtual
    8-device mesh), `TPUPlace(i)` is virtual CPU device `i`, so programs
    written for the chip run unmodified. Measurement entry points
    (`bench.py`, `chip_smoke.py`) check the platform themselves and
    refuse anything that is not `tpu`."""

    _kind = "accel"


class CUDAPlace(Place):
    """API-compat alias: resolves to the accelerator backend (TPU here)."""

    _kind = "accel"


class CUDAPinnedPlace(Place):
    _kind = "cpu"

    def __init__(self):
        super().__init__(0)


class XPUPlace(Place):
    _kind = "accel"


def _current_expected_place():
    """Default place: the accelerator if one exists, else CPU."""
    import jax

    try:
        plat = jax.default_backend()
    except Exception:
        plat = "cpu"
    if plat == "cpu":
        return CPUPlace()
    return TPUPlace(0)
