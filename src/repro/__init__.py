"""FedVision reproduction: federated visual/LM training on jax+Pallas."""
