"""FLiMS reproduction, grown into a production jax_pallas sorting stack."""
