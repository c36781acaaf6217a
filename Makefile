# Build/test entry points (the reference drives the same tasks from its
# Makefile: build tags, codegen, tests — reference Makefile:44-108).

# c++20: the interner's transparent (allocation-free) hash lookups need
# heterogeneous unordered_map support
CXX ?= g++
CXXFLAGS ?= -O3 -std=c++20 -fPIC -Wall -Wextra

.PHONY: all native proto schemas docs test bench clean analyze

# render the public JSON schemas into .schema/
schemas:
	python scripts/render_schemas.py

# repo-native static analysis (+ ruff/mypy when installed) — the CI
# static-analysis job runs the same entrypoint
analyze:
	python scripts/static_checks.py

all: native proto

# generated CLI + proto reference docs (freshness-tested in CI)
docs:
	python scripts/render_docs.py

# native libraries: tuple→graph interner (keto_tpu/graph/native.py), the
# epoll port multiplexer (keto_tpu/servers/native_mux.py), and the check
# pack walk (keto_tpu/check/native_pack.py)
native: native/libketoingest.so native/libketomux.so native/libketopack.so

native/libketoingest.so: native/ingest.cpp
	$(CXX) $(CXXFLAGS) -shared $< -o $@

native/libketomux.so: native/mux.cpp
	$(CXX) $(CXXFLAGS) -shared $< -o $@ -lpthread

native/libketopack.so: native/pack.cpp
	$(CXX) $(CXXFLAGS) -shared $< -o $@ -lpthread

# regenerate protobuf modules from the wire contract
proto:
	protoc -I proto -I /usr/include --python_out=. \
		proto/ory/keto/acl/v1alpha1/*.proto proto/grpchealth/v1/health.proto

test:
	python -m pytest tests/ -q

bench:
	python bench.py

clean:
	rm -f native/libketoingest.so native/libketomux.so native/libketopack.so
