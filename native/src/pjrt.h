// Thin RAII C++ wrapper over the PJRT C API.
//
// This is the native executor layer of dllama-tpu: where the reference hosts
// its decode loop in a C++ runtime of pthreads + sockets + SIMD kernels
// (/root/reference/src/utils.cpp:137-195, /root/reference/src/socket.cpp), the
// TPU build hosts it in a C++ process that drives the TPU through a PJRT
// plugin (libtpu.so): load plugin -> create client ->
// compile (or deserialize) the JAX-exported StableHLO decode step -> run the
// token loop with device-resident weights and KV cache. No CPU matmul
// anywhere; the C++ side only moves logits (device->host) and the sampled
// token (host->device) per step.
#pragma once

#include <cstdint>
#include <memory>
#include <stdexcept>
#include <string>
#include <vector>

#include "../third_party/pjrt_c_api.h"

namespace dllama {

// Thrown on any PJRT_Error; carries the plugin's message.
struct PjrtError : std::runtime_error {
  explicit PjrtError(const std::string& msg) : std::runtime_error(msg) {}
};

// A key/value creation option for PJRT_Client_Create (int64, string or bool).
struct ClientOption {
  std::string name;
  PJRT_NamedValue_Type type;
  std::string str_value;
  int64_t int_value = 0;
  bool bool_value = false;
  float float_value = 0.f;

  static ClientOption Int(std::string n, int64_t v);
  static ClientOption Str(std::string n, std::string v);
  static ClientOption Bool(std::string n, bool v);
  static ClientOption Float(std::string n, float v);
};

class Client;

// Device-resident array. Movable, non-copyable; frees on destruction.
class Buffer {
 public:
  Buffer() = default;
  Buffer(const PJRT_Api* api, PJRT_Buffer* buf) : api_(api), buf_(buf) {}
  Buffer(Buffer&& o) noexcept { *this = std::move(o); }
  Buffer& operator=(Buffer&& o) noexcept;
  Buffer(const Buffer&) = delete;
  Buffer& operator=(const Buffer&) = delete;
  ~Buffer();

  PJRT_Buffer* get() const { return buf_; }
  bool valid() const { return buf_ != nullptr; }
  // Blocking device->host copy. dst must hold at least host_size() bytes.
  void ToHost(void* dst, size_t dst_size) const;
  size_t host_size() const;  // bytes required by ToHost
  void reset();

 private:
  const PJRT_Api* api_ = nullptr;
  PJRT_Buffer* buf_ = nullptr;
};

// A compiled program on one or more devices. Execute() consumes/produces
// Buffers; ExecuteSharded() runs an SPMD program across N devices in one
// call (the native analog of the reference's per-layer multi-node step —
// /root/reference/src/transformer.cpp:569-728 — except the collectives live
// inside the compiled program, not in this runtime).
class Executable {
 public:
  Executable() = default;
  Executable(const PJRT_Api* api, PJRT_LoadedExecutable* exec)
      : api_(api), exec_(exec) {}
  Executable(Executable&& o) noexcept { *this = std::move(o); }
  Executable& operator=(Executable&& o) noexcept;
  Executable(const Executable&) = delete;
  ~Executable();

  size_t num_outputs() const;  // cached after the first call
  // Devices this loaded executable is bound to run on (one per shard of an
  // SPMD program; a single-device program reports one).
  size_t num_addressable_devices() const;
  // Single-device synchronous execute. Donated inputs (per the program's
  // input/output aliasing, e.g. the KV cache) are consumed: their Buffer
  // handles are invalidated by the runtime even though we don't reset them —
  // the caller must replace them with the aliased outputs and never touch
  // them again.
  std::vector<Buffer> Execute(const std::vector<PJRT_Buffer*>& args);
  // Multi-device synchronous execute: args[d] is device d's argument list
  // (every list the same length, each buffer resident on its device, in
  // the order of Executable's addressable devices). Returns one output
  // list per device. Same donation semantics as Execute, per device.
  std::vector<std::vector<Buffer>> ExecuteSharded(
      const std::vector<std::vector<PJRT_Buffer*>>& args);

 private:
  void reset();

  const PJRT_Api* api_ = nullptr;
  PJRT_LoadedExecutable* exec_ = nullptr;
  mutable size_t n_out_ = 0;  // 0 = not yet queried
};

// dlopen()s a PJRT plugin, owns the PJRT_Client.
class Client {
 public:
  // plugin_path: the manifest's `plugin` line (the exporter writes the
  // installed libtpu.so, or $DLLAMA_PJRT_PLUGIN). options: plugin-specific
  // client-creation options.
  Client(const std::string& plugin_path,
         const std::vector<ClientOption>& options);
  ~Client();
  Client(const Client&) = delete;

  const PJRT_Api* api() const { return api_; }
  std::string platform_name() const;
  size_t num_devices() const { return devices_.size(); }

  // Host->device copy onto addressable device `device_index` (default: the
  // first), blocking until the host data may be reused. Multi-device
  // programs place each weight/cache shard on its own device this way
  // before ExecuteSharded.
  Buffer ToDevice(const void* data, PJRT_Buffer_Type type,
                  const std::vector<int64_t>& dims, size_t device_index = 0);

  // Compile StableHLO bytecode ("mlir" format) with a serialized
  // xla.CompileOptionsProto (produced at export time by JAX).
  Executable Compile(const std::string& mlir_bytecode,
                     const std::string& compile_options_proto);

  // Load a pre-serialized executable (PJRT_Executable_Serialize output from
  // the same plugin version) — skips compilation entirely.
  Executable Deserialize(const std::string& serialized);

 private:
  void* dl_ = nullptr;
  const PJRT_Api* api_ = nullptr;
  PJRT_Client* client_ = nullptr;
  std::vector<PJRT_Device*> devices_;
};

// Bytes-per-element for the dtypes the exporter emits.
size_t dtype_bytes(PJRT_Buffer_Type t);
// "f32" | "bf16" | "f16" | "i32" | "u32" | "i8" | "u8" -> PJRT type.
PJRT_Buffer_Type dtype_from_string(const std::string& s);

}  // namespace dllama
