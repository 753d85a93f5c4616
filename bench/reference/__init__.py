"""Plain references for the checks that decide ``correct``.  They import
nothing of the program and take nothing it made: inputs come from the
benchmark's own generator, outputs from what the timed path returned."""
