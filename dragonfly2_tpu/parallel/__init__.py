"""The training run's device mesh and sharding rules (`mesh`: rows over `data`)."""
