"""The plain reference: numpy only, nothing of brpc_tpu, nothing of jax."""
