"""Shared path bootstrap so examples run from any cwd."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Examples pin shards=1 (ISSUE 7): they are single-connection demos whose
# output tests/test_examples.py asserts on — an inherited TRPC_SHARDS
# from a sharded-suite sweep must not change their runtime shape.
os.environ["TRPC_SHARDS"] = "1"
