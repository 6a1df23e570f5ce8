from .checkpointer import (
    Checkpointer,
    CheckpointError,
    CheckpointTimeout,
    checkpoint_files,
    decode_array,
    encode_array,
    flatten_tree,
    load_checkpoint_du,
    tree_digest,
    unflatten_tree,
)

__all__ = [
    "CheckpointError",
    "CheckpointTimeout",
    "Checkpointer",
    "checkpoint_files",
    "decode_array",
    "encode_array",
    "flatten_tree",
    "load_checkpoint_du",
    "tree_digest",
    "unflatten_tree",
]
