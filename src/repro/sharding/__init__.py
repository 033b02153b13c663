from .rules import (batch_specs, decode_state_specs, param_specs,
                    shard_tree)  # noqa: F401
