"""Architecture families, one module per family, found by the `reference`
key of a configuration file (`bench.cells.family`). Each holds the
program's field map, builds the program's parameter tree from the seed,
counts a step's operations and bytes from the published shapes, and
computes the plain float32 reference. None imports the program or takes
anything the program made."""
