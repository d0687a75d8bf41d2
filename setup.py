"""Build hook: prebuild the native chunk store into the wheel.

The C++ datacache (`flink_ml_tpu/native/datacache.cpp`) is an ordinary shared
library loaded through ctypes — not a Python extension module — so instead of
`Extension` machinery this compiles it with the system toolchain during
`build_py` and ships the `.so` as package data. Hosts without a toolchain
still work: `flink_ml_tpu.native` falls back to lazy compilation on first use
and, failing that, to the pure-Python cache tier.
"""
import hashlib
import subprocess
from pathlib import Path

from setuptools import setup
from setuptools.command.build_py import build_py


class BuildWithNative(build_py):
    def run(self):
        super().run()
        src = Path(__file__).parent / "flink_ml_tpu" / "native" / "datacache.cpp"
        # the name flink_ml_tpu.native.lib_path() looks for: source hash in it
        digest = hashlib.sha256(src.read_bytes()).hexdigest()[:12]
        out = Path(self.build_lib) / "flink_ml_tpu" / "native" / f"_datacache-{digest}.so"
        if not out.parent.exists():
            return
        cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", str(src), "-o", str(out)]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=300)
            print(f"built native datacache -> {out}")
        except Exception as e:  # toolchain-less host: lazy build remains
            print(f"skipping native datacache prebuild ({e})")


setup(cmdclass={"build_py": BuildWithNative})
