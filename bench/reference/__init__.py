"""Plain references: NumPy float64 FFTs for the transforms, and a plain
``jax.numpy`` float32 SCF at ``highest`` precision.  They import nothing
of the program under test and take nothing it made."""
