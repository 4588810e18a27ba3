"""Feature / dependency availability probes.

Counterpart of the 54 ``is_*_available`` probes in
``/root/reference/src/accelerate/utils/imports.py``.  On a JAX/TPU stack most
hardware probes collapse into PJRT platform queries; the library probes are kept
for the optional integrations (trackers, torch interop, transformers).
"""

from __future__ import annotations

import importlib.util
import functools


@functools.lru_cache
def _is_package_available(pkg_name: str) -> bool:
    return importlib.util.find_spec(pkg_name) is not None


def is_jax_available() -> bool:
    return _is_package_available("jax")


def is_flax_available() -> bool:
    return _is_package_available("flax")


def is_optax_available() -> bool:
    return _is_package_available("optax")


def is_orbax_available() -> bool:
    return _is_package_available("orbax")


def is_torch_available() -> bool:
    return _is_package_available("torch")


def is_transformers_available() -> bool:
    return _is_package_available("transformers")


def is_datasets_available() -> bool:
    return _is_package_available("datasets")


def is_safetensors_available() -> bool:
    return _is_package_available("safetensors")


def is_numpy_available() -> bool:
    return _is_package_available("numpy")


def is_einops_available() -> bool:
    return _is_package_available("einops")


@functools.lru_cache
def is_tpu_available(check_device: bool = True) -> bool:
    """True when PJRT exposes TPU devices in this process."""
    if not is_jax_available():
        return False
    if not check_device:
        return True
    try:
        import jax

        return any(d.platform == "tpu" for d in jax.devices())
    except Exception:
        return False


@functools.lru_cache
def is_pallas_available() -> bool:
    if not is_jax_available():
        return False
    try:
        from jax.experimental import pallas  # noqa: F401

        return True
    except Exception:
        return False


# ---- experiment trackers -------------------------------------------------
def is_tensorboard_available() -> bool:
    return _is_package_available("tensorboardX") or _is_package_available(
        "tensorboard"
    )


def is_wandb_available() -> bool:
    return _is_package_available("wandb")


def is_comet_ml_available() -> bool:
    return _is_package_available("comet_ml")


def is_aim_available() -> bool:
    return _is_package_available("aim")


def is_mlflow_available() -> bool:
    return _is_package_available("mlflow")


def is_clearml_available() -> bool:
    return _is_package_available("clearml")


def is_dvclive_available() -> bool:
    return _is_package_available("dvclive")


def is_swanlab_available() -> bool:
    return _is_package_available("swanlab")


def is_rich_available() -> bool:
    return _is_package_available("rich")


def is_tqdm_available() -> bool:
    return _is_package_available("tqdm")


def is_pandas_available() -> bool:
    return _is_package_available("pandas")


def is_matplotlib_available() -> bool:
    return _is_package_available("matplotlib")


def is_boto3_available() -> bool:
    return _is_package_available("boto3")


def is_psutil_available() -> bool:
    return _is_package_available("psutil")


def is_pytest_available() -> bool:
    return _is_package_available("pytest")


def is_yaml_available() -> bool:
    return _is_package_available("yaml")
