"""XLA compiles that started in the measured window, counted by the
driver's ``jax.monitoring`` listener on the backend-compile event (a
persistent-cache load counts too); None where the driver does not count
them."""


def read(rec):
    return rec.get("compiles_in_window")
