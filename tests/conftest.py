"""Test-session setup: put ``tests/`` on ``sys.path`` so test modules can
share fixtures by import (``test_partition_tables`` imports
``test_queries_jax``)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
