"""petastorm_tpu package setup.

Entry points mirror the reference's CLIs (``petastorm/setup.py`` entry_points:
petastorm-generate-metadata.py / petastorm-copy-dataset.py /
petastorm-throughput.py).
"""

from setuptools import find_packages, setup

setup(
    name='petastorm-tpu',
    version='0.1.0',
    description='TPU-native Parquet data access framework for JAX training',
    packages=find_packages(exclude=('tests',)),
    # What the code is written for and was brought up on a TPU v5e with:
    # Python 3.12, jax/jaxlib 0.9.0, libtpu 0.0.34 (see README, 'Running').
    python_requires='>=3.12',
    install_requires=[
        'numpy',
        'pyarrow>=10.0.0',
        'fsspec',
        'psutil',
        'dill',
    ],
    extras_require={
        # jax.shard_map, jax.lax.pcast, pltpu.CompilerParams and
        # jax.device_put(donate=) are used directly: no older jax has all four.
        'jax': ['jax>=0.9.0', 'flax>=0.12.3', 'optax>=0.2.6',
                'orbax-checkpoint>=0.11.32'],
        'process-pool': ['pyzmq'],
        'images': ['opencv-python'],
        'torch': ['torch'],
        'tf': ['tensorflow'],
        'test': ['pytest'],
    },
    entry_points={
        'console_scripts': [
            'petastorm-tpu-generate-metadata=petastorm_tpu.etl.metadata_cli:generate_metadata_main',
            'petastorm-tpu-metadata=petastorm_tpu.etl.metadata_cli:metadata_util_main',
            'petastorm-tpu-copy-dataset=petastorm_tpu.tools.copy_dataset:main',
            'petastorm-tpu-throughput=petastorm_tpu.benchmark.cli:main',
            'petastorm-tpu-serve=petastorm_tpu.tools.serve_cli:main',
        ],
    },
)
