"""Puts the checkout root on ``sys.path`` so tests import the ``bench``
package (tests/bench has no ``__init__.py``; pytest imports its modules
by file name)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
