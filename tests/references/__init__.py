"""Plain float32 references the tests hold the program to."""
