"""Build/install for apex_tpu (reference: the optional-extension setup.py).

Unlike the reference there are no --cpp_ext/--cuda_ext flags for the
compute path — TPU kernels are Pallas programs JIT-compiled by Mosaic, so a
plain Python install is the full-performance install.  The optional native
host runtime (flatten/bucket planner + data pipeline, apex_tpu/_native) is
built with `python setup.py build_native` (plain g++, loaded via ctypes);
without it the pure-Python fallbacks are used, mirroring the reference's
graceful degradation (README.md:90-95).
"""

import os
import subprocess
import sys

from setuptools import Command, find_packages, setup


class BuildNative(Command):
    description = "build the C++ host-runtime library (apex_tpu/_native)"
    user_options = []

    def initialize_options(self):
        pass

    def finalize_options(self):
        pass

    def run(self):
        here = os.path.dirname(os.path.abspath(__file__))
        script = os.path.join(here, "apex_tpu", "_native", "build.sh")
        subprocess.check_call(["bash", script])


setup(
    name="apex_tpu",
    version="0.1.0",
    description="TPU-native mixed-precision and distributed training "
                "toolkit (Apex-equivalent on JAX/XLA/Pallas)",
    packages=find_packages(include=["apex_tpu", "apex_tpu.*"]),
    python_requires=">=3.10",
    # written for and tested on jax/jaxlib 0.9.0 (libtpu 0.0.34 on TPU);
    # no shims for other versions are carried
    install_requires=["jax>=0.9.0,<0.10", "numpy"],
    cmdclass={"build_native": BuildNative},
)
