# Pallas TPU kernels, one package each: kernel.py (the pallas_call) and
# ref.py (a plain jax.numpy oracle).  Callers pass ``interpret``
# explicitly; nothing here switches on the backend.  None of them is on
# the served path yet (ROADMAP speed items 1, 2 and 5).
