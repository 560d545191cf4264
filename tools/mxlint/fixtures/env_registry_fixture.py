"""env-registry pass fixture (parsed, never imported)."""
import os

from mxnet_tpu import envvars


def raw_get():
    return os.environ.get("MXNET_TPU_SPANS", "1")       # env-raw-read


def raw_subscript():
    return os.environ["MXNET_TPU_FLIGHT_DIR"]           # env-raw-read


def raw_getenv():
    return os.getenv("MXNET_TPU_WATCHDOG")              # env-raw-read


def aliased():
    env = os.environ.get
    return env("MXNET_TPU_TRACE_BUFFER", 64)            # env-raw-read


def unregistered():
    return envvars.get("MXNET_TPU_NOT_A_REAL_KNOB")     # env-unregistered


def registered_ok():
    return envvars.get("MXNET_TPU_SPANS")               # clean


def non_mxnet_is_fine():
    return os.environ.get("JAX_PLATFORMS", "cpu")       # clean: not ours


def writes_are_fine():
    os.environ["MXNET_TPU_PROC_ID"] = "0"               # clean: write


def suppressed():
    return os.environ.get("MXNET_TPU_SPANS")  # mxlint: disable=env-raw-read
