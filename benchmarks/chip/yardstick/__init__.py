"""The chip benchmark's yardstick: traffic generation, the f64 reference,
the device-trace reduction, the peak table and the operation and byte
counts of the kernels.  Nothing here imports the program under test except
``harness``, which drives it, and ``control``, which switches on its plain
f32 integral images for the correctness control."""
