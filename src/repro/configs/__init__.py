from repro.configs.base import (ARCH_IDS, ModelConfig, ShapeConfig,  # noqa: F401
                                get)
