"""Shared build-on-first-use scaffold for the C++ runtime components.

One place for the g++ invocation, staleness check, atomic replace, and
double-checked-locking loader that native/dataprep.py and native/fsm.py both
use — a fix to the build logic lands once, not per component. No
pip/pybind11 involved (plain ``ctypes`` per the zero-new-dependency rule);
every caller keeps a pure-Python/numpy fallback so a machine without a
toolchain still runs."""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Callable

from ditl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

__all__ = ["NativeLib", "BUILD_DIR"]

BUILD_DIR = os.path.join(os.path.dirname(__file__), "_build")


class NativeLib:
    """Lazily builds ``csrc/<name>.cpp`` into ``_build/lib<name>.so`` and
    loads it, registering ctypes signatures via ``register``. ``get()``
    returns the CDLL or None (build/toolchain failure — caller falls back);
    the outcome is cached either way."""

    def __init__(self, name: str, register: Callable[[ctypes.CDLL], None]):
        self.name = name
        self.src = os.path.join(
            os.path.dirname(__file__), "..", "..", "csrc", f"{name}.cpp"
        )
        self.so = os.path.join(BUILD_DIR, f"lib{name}.so")
        self._register = register
        self._lock = threading.Lock()
        self._lib: ctypes.CDLL | None = None
        self._tried = False

    def _build_and_load(self) -> ctypes.CDLL | None:
        src = os.path.abspath(self.src)
        if not os.path.exists(src):
            logger.warning("native %s source missing at %s", self.name, src)
            return None
        os.makedirs(BUILD_DIR, exist_ok=True)
        if not os.path.exists(self.so) or os.path.getmtime(self.so) < os.path.getmtime(src):
            tmp = self.so + f".tmp.{os.getpid()}"
            cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", src, "-o", tmp]
            try:
                subprocess.run(cmd, check=True, capture_output=True, timeout=120)
                os.replace(tmp, self.so)  # atomic: concurrent builders don't corrupt
                logger.info("built native %s: %s", self.name, self.so)
            except (subprocess.SubprocessError, OSError) as e:
                logger.warning(
                    "native %s build failed (%s); using Python path", self.name, e
                )
                return None
        try:
            lib = ctypes.CDLL(self.so)
        except OSError as e:
            logger.warning(
                "native %s load failed (%s); using Python path", self.name, e
            )
            return None
        self._register(lib)
        return lib

    def get(self) -> ctypes.CDLL | None:
        if self._lib is None and not self._tried:
            with self._lock:
                if self._lib is None and not self._tried:
                    self._lib = self._build_and_load()
                    self._tried = True
        return self._lib

    def available(self) -> bool:
        return self.get() is not None

    @property
    def loaded(self) -> bool:
        """True once the library is in this process — unlike ``available``
        it never triggers a build, so a summary can report whether the
        native path actually ran without starting a compiler."""
        return self._lib is not None
