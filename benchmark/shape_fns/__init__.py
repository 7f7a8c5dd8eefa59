"""Shape functions: the operations and bytes a kernel needs at a cell's
static shapes, found by name from a metric's file."""
