"""Build/packaging for paddle_tpu (reference: Paddle's setup.py wheel that
embeds core.so — here the native piece is csrc/runtime.cc, built as a plain
shared library loaded via ctypes, so the wheel needs no Python C extension).

Usage:
    python setup.py bdist_wheel      # wheel with the prebuilt .so
    pip install .                    # editable-style local install
The native runtime is built from source on first import unless the packaged
.so was built from the same runtime.cc (paddle_tpu/utils/native.py keys it on
the source's content), so a source-only install works too.
"""
import os
import subprocess
import sys

from setuptools import Command, find_packages, setup
from setuptools.command.build_py import build_py


def _build_native():
    """Build the native libraries through the package's own builder
    (paddle_tpu/utils/native.py), so the wheel carries exactly the binaries
    an import of this source would build on first use."""
    from paddle_tpu.utils import native
    so, err = native._build()
    if err is not None:
        raise OSError(err)
    print("built native runtime:", so)
    print("built serving C ABI:", native.build_capi())


class BuildPyWithNative(build_py):
    def run(self):
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        try:
            _build_native()
        except (ImportError, OSError, RuntimeError,
                subprocess.CalledProcessError) as e:
            print(f"warning: native libraries not prebuilt ({e}); they are "
                  "built from source on first import", file=sys.stderr)
        super().run()


setup(
    name="paddle_tpu",
    version="0.2.0",
    description="TPU-native deep-learning framework with the PaddlePaddle "
                "capability surface (JAX/XLA/Pallas execution)",
    packages=find_packages(include=["paddle_tpu", "paddle_tpu.*"]),
    package_data={"paddle_tpu": ["csrc/*.so", "csrc/*.cc"]},
    python_requires=">=3.10",
    install_requires=["jax", "numpy"],
    cmdclass={"build_py": BuildPyWithNative},
)
