"""Build hook: compile the native C++ IO pipeline into the wheel.

ref: the reference's CMake/Makefile build producing libmxnet.so
(SURVEY §2.7); here the only native artifact is the RecordIO+JPEG
pipeline (src/io/recordio_pipeline.cc), compiled with the system g++
and bundled as package data so `pip install` ships a working
ImageRecordIter without a separate build step.  The runtime loader
(incubator_mxnet_tpu/io/native.py) prefers the packaged library and
falls back to compiling from source in a dev checkout.
"""
import os
import shutil
import subprocess

from setuptools import setup
from setuptools.command.build_py import build_py


class BuildWithNativeIO(build_py):
    def run(self):
        here = os.path.dirname(os.path.abspath(__file__))
        src = os.path.join(here, "src", "io", "recordio_pipeline.cc")
        out = os.path.join(here, "incubator_mxnet_tpu", "io",
                           "libmxtpu_io.so")
        try:
            # the ONE compile recipe lives in io/native.py
            import sys
            sys.path.insert(0, here)
            from incubator_mxnet_tpu.io.native import build_library
            build_library(force=True, src=src, out=out)
            print("built native io pipeline ->", out)
        except Exception as e:
            # pure-python install still works (python RecordIO fallback)
            print("WARNING: native io build skipped:", e)
        # flat C ABI (c_api.h surface) — optional: the python package
        # does not depend on it, but a wheel that carries it lets C/C++
        # clients dlopen the installed library
        capi_out = os.path.join(here, "incubator_mxnet_tpu",
                                "libmxtpu_c.so")
        try:
            # load the recipe module directly from its file: a package
            # import would execute incubator_mxnet_tpu/__init__ (jax
            # import), which build environments may not have
            import importlib.util
            spec = importlib.util.spec_from_file_location(
                "_capi_build", os.path.join(here, "incubator_mxnet_tpu",
                                            "_capi_build.py"))
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            mod.build_capi_library(capi_out)
            print("built c_api ->", capi_out)
        except Exception as e:
            print("WARNING: c_api build skipped:", e)
        super().run()
        # place the artifacts into the build tree as package data
        for rel in (("io", "libmxtpu_io.so"), ("libmxtpu_c.so",)):
            built = os.path.join(here, "incubator_mxnet_tpu", *rel)
            if os.path.exists(built):
                dst = os.path.join(self.build_lib,
                                   "incubator_mxnet_tpu", *rel)
                os.makedirs(os.path.dirname(dst), exist_ok=True)
                shutil.copyfile(built, dst)


setup(cmdclass={"build_py": BuildWithNativeIO},
      package_data={"incubator_mxnet_tpu.io": ["libmxtpu_io.so"],
                    "incubator_mxnet_tpu": ["libmxtpu_c.so"]})
