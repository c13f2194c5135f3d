"""Packaging for elasticdl_tpu (reference: setup.py:1-19 exposes the
`elasticdl` console script; here both spellings map to the client CLI).

The C++ RecordIO indexer (elasticdl_tpu/data/recordio_cpp/recordio.cc)
is compiled lazily at first use via the in-tree g++ path
(data/recordio.py:_load_native) with a pure-Python fallback, so the
wheel needs no build-time toolchain.
"""

from setuptools import find_packages, setup

setup(
    name="elasticdl_tpu",
    version="0.3.0",
    description=(
        "TPU-native elastic deep learning: Kubernetes-elastic PS "
        "training on JAX/XLA"
    ),
    packages=find_packages(include=["elasticdl_tpu", "elasticdl_tpu.*"]),
    package_data={
        "elasticdl_tpu.data": ["recordio_cpp/*.cc"],
        "elasticdl_tpu.master": ["embedding_cpp/*.cc"],
        "elasticdl_tpu.chaos": ["traces/*.json"],
    },
    python_requires=">=3.12",
    install_requires=[
        "numpy",
        "jax",
        "flax",
        "optax",
        "msgpack",
        "grpcio",
    ],
    extras_require={
        "k8s": ["kubernetes"],
    },
    entry_points={
        "console_scripts": [
            "elasticdl_tpu=elasticdl_tpu.client.main:main",
            "elasticdl=elasticdl_tpu.client.main:main",
        ]
    },
)
