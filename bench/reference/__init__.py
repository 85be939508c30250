"""The benchmark's plain float32 reference: a dense GQA decoder with
tri-LoRA adapters and the seeded weights it runs on.  It imports nothing
of the program under test."""
