"""Model families: seeded checkpoints, plain references, op/byte counts."""
