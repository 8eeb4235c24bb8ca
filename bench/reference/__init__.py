"""Plain float32 layers of the references; imports nothing of the program
under test."""
